"""Watershed stack tests: kernel oracles + end-to-end workflow properties
(reference test style: test/watershed/test_watershed.py:53-70 — no zeros
unless masked, fragment count sanity)."""

import numpy as np
import pytest
from scipy import ndimage

import jax.numpy as jnp

from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build
from cluster_tools_tpu.workflows.watershed import WatershedWorkflow


def _boundary_volume(shape, n_cells=4, seed=0, sigma=1.0):
    """Synthetic boundary map: voronoi-ish cells with smooth boundaries."""
    rng = np.random.RandomState(seed)
    points = rng.rand(n_cells, len(shape)) * np.array(shape)
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    coords = np.stack([g.ravel() for g in grids], axis=1)
    d = np.linalg.norm(coords[:, None, :] - points[None, :, :], axis=2)
    d.sort(axis=1)
    boundary = np.exp(-(d[:, 1] - d[:, 0]) ** 2 / 4.0).reshape(shape)
    return ndimage.gaussian_filter(boundary, sigma).astype("float32")


def test_edt_matches_scipy():
    from cluster_tools_tpu.ops.edt import distance_transform_edt

    rng = np.random.RandomState(3)
    mask = rng.rand(14, 18, 22) > 0.4
    ours = np.asarray(distance_transform_edt(jnp.asarray(mask)))
    ref = ndimage.distance_transform_edt(mask)
    assert np.abs(ours - ref).max() < 1e-4
    # anisotropic sampling
    ours = np.asarray(distance_transform_edt(jnp.asarray(mask),
                                             sampling=(3.0, 1.0, 1.0)))
    ref = ndimage.distance_transform_edt(mask, sampling=(3.0, 1.0, 1.0))
    assert np.abs(ours - ref).max() < 1e-4


def test_gaussian_filters_match_scipy():
    from cluster_tools_tpu.ops.filters import (
        gaussian, gaussian_gradient_magnitude, laplacian_of_gaussian,
    )

    rng = np.random.RandomState(0)
    x = rng.rand(20, 24, 28).astype("float32")
    assert np.abs(np.asarray(gaussian(jnp.asarray(x), 1.5))
                  - ndimage.gaussian_filter(x, 1.5, mode="reflect")).max() < 1e-2
    assert np.abs(np.asarray(gaussian_gradient_magnitude(jnp.asarray(x), 1.2))
                  - ndimage.gaussian_gradient_magnitude(x, 1.2, mode="reflect")).max() < 1e-2
    assert np.abs(np.asarray(laplacian_of_gaussian(jnp.asarray(x), 1.2))
                  - ndimage.gaussian_laplace(x, 1.2, mode="reflect")).max() < 1e-2


@pytest.mark.parametrize("method", ["basins", "flood"])
def test_seeded_watershed_properties(method):
    from cluster_tools_tpu.ops.watershed import seeded_watershed

    # two basins split by a ridge
    h = np.zeros((20, 30), "float32")
    h[:, 14:16] = 1.0
    seeds = np.zeros((20, 30), "int32")
    seeds[10, 4], seeds[10, 25] = 1, 2
    ws = np.asarray(seeded_watershed(jnp.asarray(h), jnp.asarray(seeds),
                                     method=method))
    assert (ws > 0).all()
    assert (ws[:, :14] == 1).all()
    assert (ws[:, 16:] == 2).all()
    # seeds keep their labels
    assert ws[10, 4] == 1 and ws[10, 25] == 2


@pytest.mark.parametrize("method", ["basins", "flood"])
def test_seeded_watershed_respects_mask(method):
    from cluster_tools_tpu.ops.watershed import seeded_watershed

    h = np.random.RandomState(0).rand(16, 16).astype("float32")
    seeds = np.zeros((16, 16), "int32")
    seeds[2, 2] = 1
    mask = np.ones((16, 16), bool)
    mask[:, 8:] = False
    ws = np.asarray(seeded_watershed(jnp.asarray(h), jnp.asarray(seeds),
                                     jnp.asarray(mask), method=method))
    assert (ws[:, 8:] == 0).all()
    assert (ws[:, :8] == 1).all()


def test_basins_dense_seed_regrow_keeps_adjacent_labels():
    # adjacent different-id seed clusters must NOT merge (the size-filter
    # regrow passes dense kept fragments as seeds)
    from cluster_tools_tpu.ops.watershed import seeded_watershed_basins

    h = np.random.RandomState(1).rand(12, 12).astype("float32")
    seeds = np.zeros((12, 12), "int32")
    seeds[:, :6] = 3
    seeds[:, 6:] = 7  # touching block of a different id
    seeds[5, 5] = 0   # one free voxel to fill
    ws = np.asarray(seeded_watershed_basins(jnp.asarray(h),
                                            jnp.asarray(seeds)))
    assert (ws[:, :5] == 3).all()
    assert (ws[:, 6:] == 7).all()
    assert ws[5, 5] in (3, 7)


def test_seeded_watershed_unknown_method_raises():
    from cluster_tools_tpu.ops.watershed import seeded_watershed

    with pytest.raises(ValueError, match="unknown watershed method"):
        seeded_watershed(jnp.zeros((4, 4)), jnp.zeros((4, 4), "int32"),
                         method="basin")


@pytest.mark.parametrize("target", ["inline"])
def test_watershed_workflow_end_to_end(tmp_workdir, tmp_path, target):
    tmp_folder, config_dir = tmp_workdir
    shape = (24, 24, 24)
    vol = _boundary_volume(shape, n_cells=6)

    path = str(tmp_path / "data.n5")
    with file_reader(path) as f:
        f.require_dataset("boundaries", shape=shape, chunks=(12, 12, 12),
                          dtype="float32")[...] = vol

    wf = WatershedWorkflow(
        input_path=path, input_key="boundaries",
        output_path=path, output_key="ws",
        tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=2, target=target)
    assert build([wf], raise_on_failure=True)

    with file_reader(path, "r") as f:
        ws = f["ws"][...]
        max_id = f["ws"].attrs["maxId"]
    # reference oracle: no zeros without mask (test_watershed.py:53-70)
    assert (ws > 0).all()
    # consecutive labels after relabel
    uniques = np.unique(ws)
    assert uniques[0] == 1
    assert uniques[-1] == len(uniques)
    assert max_id == len(uniques)
    # sane fragment count for 6 cells across 8 blocks (fragments over-segment)
    assert 2 <= len(uniques) < 500


@pytest.mark.slow
def test_watershed_workflow_with_mask(tmp_workdir, tmp_path):
    tmp_folder, config_dir = tmp_workdir
    shape = (20, 20, 20)
    vol = _boundary_volume(shape, n_cells=4)
    mask = np.zeros(shape, "uint8")
    mask[:, :10, :] = 1

    path = str(tmp_path / "data.n5")
    with file_reader(path) as f:
        f.require_dataset("boundaries", shape=shape, chunks=(10, 10, 10),
                          dtype="float32")[...] = vol
        f.require_dataset("mask", shape=shape, chunks=(10, 10, 10),
                          dtype="uint8")[...] = mask

    wf = WatershedWorkflow(
        input_path=path, input_key="boundaries",
        output_path=path, output_key="ws",
        mask_path=path, mask_key="mask",
        tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=2, target="inline")
    assert build([wf], raise_on_failure=True)
    with file_reader(path, "r") as f:
        ws = f["ws"][...]
    assert (ws[:, 10:, :] == 0).all()
    assert (ws[:, :10, :] > 0).all()


@pytest.mark.slow
def test_watershed_label_offsets_never_collide(tmp_workdir, tmp_path):
    """Halo larger than the block: uncompacted outer-block CC roots would
    exceed the offset unit and collide across blocks (regression)."""
    from cluster_tools_tpu.core.config import ConfigDir

    tmp_folder, config_dir = tmp_workdir
    ConfigDir(config_dir).write_global_config({"block_shape": [8, 8, 8]})
    shape = (16, 16, 16)
    vol = _boundary_volume(shape, n_cells=5, seed=2)
    path = str(tmp_path / "d.n5")
    with file_reader(path) as f:
        f.require_dataset("b", shape=shape, chunks=(8, 8, 8),
                          dtype="float32")[...] = vol
    wf = WatershedWorkflow(
        input_path=path, input_key="b", output_path=path, output_key="ws",
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=1,
        target="inline")
    assert build([wf], raise_on_failure=True)
    with file_reader(path, "r") as f:
        ws = f["ws"][...]
    # no fragment may span blocks (labels are per-block before stitching):
    # each label's voxels must lie inside exactly one 8^3 block
    from cluster_tools_tpu.core.blocking import Blocking

    blocking = Blocking(shape, [8, 8, 8])
    owner = np.zeros(shape, dtype=int)
    for bid in range(blocking.n_blocks):
        owner[blocking.get_block(bid).bb] = bid
    for lab in np.unique(ws[ws > 0]):
        assert len(np.unique(owner[ws == lab])) == 1, f"label {lab} crosses blocks"


def test_watershed_2d_mode_slices_independent(tmp_workdir, tmp_path):
    from cluster_tools_tpu.core.config import ConfigDir

    tmp_folder, config_dir = tmp_workdir
    cfgd = ConfigDir(config_dir)
    cfgd.write_global_config({"block_shape": [16, 16, 16]})
    cfgd.write_task_config("watershed", {
        "apply_dt_2d": True, "apply_ws_2d": True, "halo": [0, 2, 2],
        "sigma_seeds": 1.0, "sigma_weights": 1.0, "size_filter": 4})
    shape = (4, 16, 16)
    vol = np.stack([_boundary_volume((16, 16), n_cells=3, seed=s)
                    for s in range(4)]).astype("float32")
    path = str(tmp_path / "d.n5")
    with file_reader(path) as f:
        f.require_dataset("b", shape=shape, chunks=(4, 16, 16),
                          dtype="float32")[...] = vol
    wf = WatershedWorkflow(
        input_path=path, input_key="b", output_path=path, output_key="ws",
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=1,
        target="inline")
    assert build([wf], raise_on_failure=True)
    with file_reader(path, "r") as f:
        ws = f["ws"][...]
    assert (ws > 0).all()
    # labels must not span z-slices
    for lab in np.unique(ws):
        zs = np.unique(np.nonzero(ws == lab)[0])
        assert len(zs) == 1, f"label {lab} spans slices {zs}"


@pytest.mark.slow
def test_streamed_pipeline_matches_blockwise():
    """run_ws_blocks_stream (the fused bench/deployment path) produces the
    same fragments as run_ws_block on the 3d no-mask path."""
    from cluster_tools_tpu.workflows.watershed import (run_ws_block,
                                                       run_ws_blocks_stream)

    vol = _boundary_volume((16, 24, 24), n_cells=4)
    cfg = {"threshold": 0.5, "sigma_seeds": 2.0, "sigma_weights": 2.0,
           "alpha": 0.8, "size_filter": 0}
    single = run_ws_block(vol, cfg)
    streamed = run_ws_blocks_stream([vol, vol], cfg)
    np.testing.assert_array_equal(streamed[0], single)
    np.testing.assert_array_equal(streamed[1], single)


@pytest.mark.slow
def test_watershed_fragment_purity():
    """Regression: the priority-flood fill must not leak labels across
    ridges (the unordered fill silently merged basins: interior purity
    ~0.7 on this geometry)."""
    shape = (32, 64, 64)
    rng = np.random.RandomState(0)
    pts = (rng.rand(8, 3) * np.array(shape)).astype("float32")
    grids = np.meshgrid(*[np.arange(s, dtype="float32") for s in shape],
                        indexing="ij")
    d1 = np.full(shape, np.inf, "float32")
    d2 = np.full(shape, np.inf, "float32")
    lab = np.zeros(shape, "uint64")
    for i, p in enumerate(pts):
        dist = np.sqrt(sum((g - c) ** 2 for g, c in zip(grids, p)))
        nearer = dist < d1
        d2 = np.where(nearer, d1, np.minimum(d2, dist))
        lab = np.where(nearer, i + 1, lab)
        d1 = np.where(nearer, dist, d1)
    bnd = np.exp(-0.5 * ((d2 - d1) / 2.0) ** 2).astype("float32")

    from cluster_tools_tpu.ops.overlaps import count_overlaps
    from cluster_tools_tpu.workflows.watershed import run_ws_block

    cfg = {"threshold": 0.4, "sigma_seeds": 2.0, "sigma_weights": 2.0,
           "alpha": 0.8, "size_filter": 50}
    ws = run_ws_block(bnd, cfg)
    assert (ws > 0).all()

    interior = (d2 - d1) > 4.0
    iw, ig, counts = count_overlaps(np.where(interior, ws, 0),
                                    np.where(interior, lab, 0))
    keep = iw != 0
    iw, counts = iw[keep], counts[keep]
    tot = {}
    best = {}
    for w, c in zip(iw, counts):
        tot[w] = tot.get(w, 0) + int(c)
        best[w] = max(best.get(w, 0), int(c))
    purity = np.array([best[w] / tot[w] for w in tot])
    assert purity.min() > 0.97, purity


def test_suppress_maxima():
    """Distance-based NMS (reference: nonMaximumDistanceSuppression path,
    watershed.py:199-203): weaker maxima inside a stronger maximum's
    dt-radius are dropped; points outside survive."""
    from cluster_tools_tpu.workflows.watershed import suppress_maxima

    pts = np.array([[0, 0, 0], [0, 0, 3], [0, 0, 8]], "int64")
    radii = np.array([5.0, 1.0, 2.0])
    kept = suppress_maxima(pts, radii)
    # strongest kept; [0,0,3] is within radius 5 of it; [0,0,8] is outside
    assert {tuple(p) for p in kept} == {(0, 0, 0), (0, 0, 8)}
    # empty input passes through
    assert len(suppress_maxima(np.zeros((0, 3), "int64"),
                               np.zeros(0))) == 0


@pytest.mark.slow
def test_watershed_nms_reduces_fragments(tmp_workdir, tmp_path):
    """non_maximum_suppression merges duplicate seeds on broad plateaus ->
    fewer fragments, still a complete (no zeros) labeling."""
    from cluster_tools_tpu.workflows.watershed import run_ws_block

    rng = np.random.RandomState(0)
    # one wide cell interior with a noisy DT -> several spurious maxima
    bmap = np.ones((24, 24, 24), "float32")
    bmap[2:22, 2:22, 2:22] = 0.05
    bmap += rng.rand(24, 24, 24).astype("float32") * 0.04
    cfg = {"threshold": 0.3, "sigma_seeds": 0.0, "size_filter": 0,
           "apply_ws_2d": False}
    ws_plain = run_ws_block(bmap, cfg)
    ws_nms = run_ws_block(bmap, {**cfg, "non_maximum_suppression": True})
    assert (ws_nms > 0).all()
    n_plain = len(np.unique(ws_plain))
    n_nms = len(np.unique(ws_nms))
    assert n_nms <= n_plain
    assert n_nms >= 1


@pytest.mark.slow
def test_streamed_pipeline_matches_blockwise_with_size_filter():
    """Both streamed size-filter paths — fused on-device (bincount + regrow
    inside the jitted pipeline, the accelerator default) and host-side (the
    CPU-backend default) — match run_ws_block's host size_filter path."""
    from cluster_tools_tpu.workflows.watershed import (run_ws_block,
                                                       run_ws_blocks_stream)

    vol = _boundary_volume((16, 24, 24), n_cells=6)
    cfg = {"threshold": 0.5, "sigma_seeds": 2.0, "sigma_weights": 2.0,
           "alpha": 0.8, "size_filter": 40}
    single = run_ws_block(vol, cfg)
    for fuse in (True, False):
        streamed = run_ws_blocks_stream(
            [vol], {**cfg, "fuse_size_filter": fuse})[0]
        np.testing.assert_array_equal(streamed, single)


def test_pallas_minplus_kernel_matches_oracle():
    """The Pallas min-plus EDT kernel (interpret mode on CPU) equals the
    direct broadcast min-plus, including non-multiple-of-128 shapes where
    the BIG padding must never win."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.edt import _minplus_pallas

    rng = np.random.RandomState(0)
    for m, n, s in [(13, 37, 1.5), (4, 130, 1.0), (20, 129, 2.0)]:
        flat = rng.rand(m, n).astype("float32") * 50
        out = np.asarray(_minplus_pallas(jnp.asarray(flat), s,
                                         interpret=True))
        idx = np.arange(n, dtype="float32") * s
        cost = (idx[:, None] - idx[None, :]) ** 2
        expect = (flat[:, None, :] + cost[None]).min(-1)
        np.testing.assert_allclose(out, expect, rtol=1e-6,
                                   err_msg=str((m, n, s)))


def test_edt_axes_and_vmap_safety():
    """axes=(1,2) folds slices into the scanline batch (per-slice 2d EDT,
    no vmap); and vmapping the pallas kernel must stay correct — jax's
    pallas batching rule would scramble the grid's program_id axes, which
    sequential_vmap prevents (regression)."""
    import jax

    from cluster_tools_tpu.ops.edt import (_minplus_pallas,
                                           distance_transform_edt)

    rng = np.random.RandomState(0)
    mask = rng.rand(5, 30, 31) > 0.4
    got = np.asarray(distance_transform_edt(jnp.asarray(mask), axes=(1, 2)))
    want = np.stack([ndimage.distance_transform_edt(m) for m in mask])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    f = rng.rand(3, 6, 37).astype("float32") * 10
    out = np.asarray(jax.vmap(
        lambda x: _minplus_pallas(x, 1.0, interpret=True))(jnp.asarray(f)))
    idx = np.arange(37, dtype="float32")
    cost = (idx[:, None] - idx[None, :]) ** 2
    want = (f[:, :, None, :] + cost[None, None]).min(-1)
    np.testing.assert_allclose(out, want, rtol=1e-5)


@pytest.mark.slow
def test_host_watershed_block_quality():
    """run_ws_block_host (scipy reference-faithful path) segments the
    synthetic boundary volume comparably to the device path."""
    from cluster_tools_tpu.workflows.watershed import (run_ws_block,
                                                       run_ws_block_host)

    vol = _boundary_volume((24, 24, 24), n_cells=6)
    cfg = {"threshold": 0.4, "sigma_seeds": 1.5, "sigma_weights": 1.5,
           "size_filter": 10, "alpha": 0.8}
    host = run_ws_block_host(vol, cfg)
    dev = run_ws_block(vol, cfg)
    assert host.shape == vol.shape
    # both produce a dense fragmentation of comparable granularity
    n_host = len(np.unique(host[host > 0]))
    n_dev = len(np.unique(dev[dev > 0]))
    assert n_host >= 2 and n_dev >= 2
    assert n_host < 8 * n_dev and n_dev < 8 * n_host
    # host fragments respect the mask argument
    mask = np.ones(vol.shape, bool)
    mask[:, :, 12:] = False
    host_m = run_ws_block_host(vol, cfg, mask=mask)
    assert (host_m[:, :, 12:] == 0).all()


@pytest.mark.parametrize("target", ["inline", "threads"])
def test_watershed_workflow_records_host_stages(tmp_workdir, tmp_path,
                                                target):
    """The streamed watershed task times its host work as stages (and so
    as ``ctt.stage.*`` spans in a profiler trace): block reads on the
    prefetch thread, the wait for them, the device waits, the per-block
    relabel map and the fragment writes.  Where the jobs run in the
    driver (``inline``) it writes every block with its final id and no
    relabel follows; elsewhere (``threads``) the relabel passes do, with
    FindUniques timing its reads and scans and FindLabeling its scan."""
    import glob
    import json
    import os

    tmp_folder, config_dir = tmp_workdir
    shape = (24, 24, 24)
    path = str(tmp_path / "data.n5")
    with file_reader(path) as f:
        f.require_dataset("boundaries", shape=shape, chunks=(12, 12, 12),
                          dtype="float32")[...] = _boundary_volume(shape)
    assert build([WatershedWorkflow(
        input_path=path, input_key="boundaries", output_path=path,
        output_key="ws", tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=1, target=target)], raise_on_failure=True)
    stages = {}
    for sf in glob.glob(os.path.join(tmp_folder, "*.status")):
        with open(sf) as f:
            st = json.load(f)
        stages[st["task"].split("_relabel")[0]] = st["stage_counts"]
    assert {"store-read", "prefetch-wait", "sync-execute", "host-map",
            "store-write"} <= set(stages["watershed"]), stages
    # one fragment write per block of the conftest's [10, 10, 10] grid
    assert stages["watershed"]["store-write"] == 27
    if target == "inline":
        assert stages["watershed"]["final-ids"] == 27
        assert set(stages) == {"watershed"}, stages
    else:
        assert "final-ids" not in stages["watershed"]
        assert {"store-read", "host-scan"} <= set(stages["find_uniques"])
        assert "host-scan" in stages["find_labeling"]


def _ws_case(tmp_path, case):
    """Input, configuration and mask of one byte-identity case: a config
    dir, the input path and the workflow's mask kwargs."""
    import json

    from cluster_tools_tpu.core.config import ConfigDir

    gconf = {"block_shape": [10, 10, 10], "max_num_retries": 0}
    tconf = {"halo": [2, 4, 4], "sigma_seeds": 1.0, "size_filter": 4}
    shape = (24, 24, 24)
    mask_kw = {}
    if case == "ws_2d":
        shape = (8, 24, 24)
        gconf["block_shape"] = [4, 12, 12]
        tconf.update(apply_dt_2d=True, apply_ws_2d=True, halo=[0, 2, 2],
                     sigma_weights=1.0)
    elif case == "roi":
        gconf.update(roi_begin=[10, 0, 10], roi_end=[24, 20, 24])
    elif case == "block_list":
        blocks = str(tmp_path / "blocks.json")
        with open(blocks, "w") as f:
            json.dump([26, 3, 14, 0, 9, 21, 5, 17], f)
        gconf["block_list_path"] = blocks
    vol = _boundary_volume(shape, n_cells=8, seed=3)
    path = str(tmp_path / "in.n5")
    with file_reader(path) as f:
        f.require_dataset("b", shape=shape, chunks=(12, 12, 12),
                          dtype="float32")[...] = vol
        if case == "mask":
            # block 0's whole outer window is background: the block is
            # skipped, and its neighbours are partly masked
            mask = np.ones(shape, "uint8")
            mask[:12, :12, :12] = 0
            f.require_dataset("m", shape=shape, chunks=(12, 12, 12),
                              dtype="uint8")[...] = mask
            mask_kw = {"mask_path": path, "mask_key": "m"}
    config_dir = str(tmp_path / "configs")
    cd = ConfigDir(config_dir)
    cd.write_global_config(gconf)
    cd.write_task_config("watershed", tconf)
    return config_dir, path, mask_kw


def _run_ws(tmp_path, config_dir, path, mask_kw, target, key):
    """One WatershedWorkflow into ``key``: its volume, maxId and tmp
    folder."""
    tmp_folder = str(tmp_path / f"tmp_{key}")
    assert build([WatershedWorkflow(
        input_path=path, input_key="b", output_path=path, output_key=key,
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2,
        target=target, **mask_kw)], raise_on_failure=True)
    with file_reader(path, "r") as f:
        return f[key][...], f[key].attrs["maxId"], tmp_folder


@pytest.mark.parametrize("case", ["streamed", "ws_2d", "mask", "roi",
                                  "block_list"])
def test_final_ids_match_relabel(tmp_path, case):
    """The single pass that writes final ids (``inline``: one job in
    block-id order, a running offset) gives the volume and maxId byte for
    byte that the block offsets and the relabel passes give (``threads``
    still chains RelabelWorkflow)."""
    import os

    config_dir, path, mask_kw = _ws_case(tmp_path, case)
    final, final_max, final_tmp = _run_ws(tmp_path, config_dir, path,
                                          mask_kw, "inline", "final")
    relab, relab_max, relab_tmp = _run_ws(tmp_path, config_dir, path,
                                          mask_kw, "threads", "relabel")
    assert os.path.exists(os.path.join(relab_tmp, "write_relabel_ws.status"))
    assert not os.path.exists(
        os.path.join(final_tmp, "find_uniques_relabel_ws.status"))
    assert final.dtype == relab.dtype == np.uint64
    np.testing.assert_array_equal(final, relab)
    assert final_max == relab_max == len(np.unique(final[final > 0]))
    # several blocks each with several ids: the offsets were exercised
    assert final_max > 8


def test_final_ids_retry_matches_unbroken_run(tmp_path, monkeypatch):
    """The single job fails at its third fragment write; the retry over
    the blocks it did not log starts from the id counts it recorded, and
    the volume and maxId equal an unbroken run's."""
    import json
    import os

    from cluster_tools_tpu.core import storage
    from cluster_tools_tpu.core.config import ConfigDir

    config_dir, path, mask_kw = _ws_case(tmp_path, "streamed")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "max_num_retries": 1})
    whole, whole_max, _ = _run_ws(tmp_path, config_dir, path, mask_kw,
                                  "inline", "whole")

    writes = []
    setitem = storage.Dataset.__setitem__

    def flaky(self, bb, value):
        if self.path.endswith("retried"):
            writes.append(bb)
            if len(writes) == 3:
                raise OSError("store write lost")
        setitem(self, bb, value)

    monkeypatch.setattr(storage.Dataset, "__setitem__", flaky)
    retried, retried_max, tmp = _run_ws(tmp_path, config_dir, path,
                                        mask_kw, "inline", "retried")
    with open(os.path.join(tmp, "watershed.status")) as f:
        assert json.load(f)["retries"] == 1
    # 27 blocks written, the third twice
    assert len(writes) == 28
    np.testing.assert_array_equal(retried, whole)
    assert retried_max == whole_max
