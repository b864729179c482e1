"""Mutex watershed stack: ops-level kernel vs ground-truth partition, and
the blockwise single-pass / two-pass workflows (reference test style:
synthetic affinities with a known segmentation as oracle)."""

import numpy as np
import pytest
from scipy import ndimage

from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build
from cluster_tools_tpu.workflows.mutex_watershed import (
    MwsWorkflow, TwoPassMwsWorkflow,
)

OFFSETS = [[-1, 0, 0], [0, -1, 0], [0, 0, -1],
           [-4, 0, 0], [0, -4, 0], [0, 0, -4]]


def _partitions_equal(a, b, ignore_zero=True):
    if ignore_zero and not ((a == 0) == (b == 0)).all():
        return False
    fg = (a != 0) if ignore_zero else np.ones(a.shape, bool)
    pairs = np.unique(np.stack([a[fg], b[fg]]), axis=1)
    return (len(np.unique(pairs[0])) == pairs.shape[1]
            and len(np.unique(pairs[1])) == pairs.shape[1])


def _make_gt(shape, seed=0):
    """Blocky ground-truth labels: seeded nearest-centroid regions (each
    connected, spanning multiple processing blocks)."""
    rng = np.random.RandomState(seed)
    n_seeds = 6
    points = np.stack([rng.randint(0, s, n_seeds) for s in shape], axis=1)
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    dists = np.stack([
        sum((g - p[i]) ** 2 for i, g in enumerate(grids))
        for p in points])
    return (np.argmin(dists, axis=0) + 1).astype("uint64")


def _affs_from_gt(gt, offsets, lo=0.0, hi=0.9):
    affs = np.full((len(offsets),) + gt.shape, lo, dtype="float32")
    for c, off in enumerate(offsets):
        sl_a, sl_b = [], []
        for o, s in zip(off, gt.shape):
            sl_a.append(slice(0, s - abs(o)) if o >= 0 else slice(-o, s))
            sl_b.append(slice(o, s) if o >= 0 else slice(0, s + o))
        same = gt[tuple(sl_a)] == gt[tuple(sl_b)]
        affs[c][tuple(sl_a)] = np.where(same, hi, lo)
    return affs


def test_mws_segmentation_recovers_gt():
    from cluster_tools_tpu.ops.mws import mutex_watershed_segmentation

    gt = _make_gt((16, 16, 16))
    affs = _affs_from_gt(gt, OFFSETS)
    seg = mutex_watershed_segmentation(affs, OFFSETS)
    assert _partitions_equal(seg, gt, ignore_zero=False)


def test_mws_segmentation_mask_and_strides():
    from cluster_tools_tpu.ops.mws import mutex_watershed_segmentation

    gt = _make_gt((16, 16, 16), seed=3)
    affs = _affs_from_gt(gt, OFFSETS)
    mask = np.zeros(gt.shape, bool)
    mask[2:14, 2:14, 2:14] = True
    seg = mutex_watershed_segmentation(affs, OFFSETS, strides=[2, 2, 2],
                                       mask=mask)
    assert (seg[~mask] == 0).all()
    assert (seg[mask] > 0).all()
    # within the mask the partition still matches ground truth
    masked_gt = np.where(mask, gt, 0)
    assert _partitions_equal(seg, masked_gt)


def test_mws_seeded_respects_seeds():
    from cluster_tools_tpu.ops.mws import mutex_watershed_segmentation

    gt = _make_gt((12, 12, 12), seed=1)
    affs = _affs_from_gt(gt, OFFSETS)
    # seed half the volume with ground-truth labels (as pass-2 sees pass-1)
    seeds = np.zeros(gt.shape, dtype="uint64")
    seeds[:6] = gt[:6] + 100
    seg, assignments = mutex_watershed_segmentation(
        affs, OFFSETS, seeds=seeds, return_seed_assignments=True)
    # no segment may span two different seed labels
    fg = seeds != 0
    pairs = np.unique(np.stack([seg[fg], seeds[fg]]), axis=1)
    seg_ids, counts = np.unique(pairs[0], return_counts=True)
    assert (counts == 1).all()
    assert len(assignments) == pairs.shape[1]
    assert _partitions_equal(seg, gt, ignore_zero=False)


@pytest.mark.parametrize("target", ["inline", "local"])
def test_mws_workflow(tmp_workdir, tmp_path, target):
    tmp_folder, config_dir = tmp_workdir
    shape = (20, 20, 20)
    gt = _make_gt(shape)
    affs = _affs_from_gt(gt, OFFSETS)

    path = str(tmp_path / "data.n5")
    with file_reader(path) as f:
        ds = f.require_dataset("affs", shape=affs.shape,
                               chunks=(1, 10, 10, 10), dtype="float32")
        ds[...] = affs

    wf = MwsWorkflow(
        input_path=path, input_key="affs", output_path=path, output_key="mws",
        offsets=OFFSETS, tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=4, target=target)
    assert build([wf], raise_on_failure=True)

    with file_reader(path, "r") as f:
        seg = f["mws"][...]
    # single-pass, no stitching: expect the per-block 6-connected refinement
    # of the gt partition (affinities are 0 across gt boundaries, so no
    # cross-region merges happen even where no in-block mutex pair exists)
    expected = np.zeros(shape, dtype="uint64")
    next_id = 1
    for z in range(0, shape[0], 10):
        for y in range(0, shape[1], 10):
            for x in range(0, shape[2], 10):
                bb = np.s_[z:z + 10, y:y + 10, x:x + 10]
                block_gt = gt[bb]
                lab = np.zeros_like(block_gt)
                n = 0
                for gid in np.unique(block_gt):
                    comp, k = ndimage.label(block_gt == gid)
                    lab[comp > 0] = comp[comp > 0] + n
                    n += k
                expected[bb] = lab + (next_id - 1)
                next_id += n
    assert _partitions_equal(seg, expected, ignore_zero=False)
    # labels are consecutive after the relabel workflow
    assert seg.max() == len(np.unique(seg))


@pytest.mark.parametrize("target", ["inline", "local"])
def test_two_pass_mws_workflow_recovers_gt(tmp_workdir, tmp_path, target):
    tmp_folder, config_dir = tmp_workdir
    shape = (20, 20, 20)
    gt = _make_gt(shape, seed=2)
    affs = _affs_from_gt(gt, OFFSETS)

    path = str(tmp_path / "data.n5")
    with file_reader(path) as f:
        ds = f.require_dataset("affs", shape=affs.shape,
                               chunks=(1, 10, 10, 10), dtype="float32")
        ds[...] = affs

    wf = TwoPassMwsWorkflow(
        input_path=path, input_key="affs", output_path=path,
        output_key="mws2p", offsets=OFFSETS, halo=[4, 4, 4],
        tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=4, target=target)
    assert build([wf], raise_on_failure=True)

    with file_reader(path, "r") as f:
        seg = f["mws2p"][...]
    # stitched result must recover the ground-truth partition refined to
    # 6-connected components (nearest-centroid regions are not guaranteed
    # 6-connected, and attractive edges only span direct neighbors)
    expected = np.zeros(shape, dtype="uint64")
    n = 0
    for gid in np.unique(gt):
        comp, k = ndimage.label(gt == gid)
        expected[comp > 0] = comp[comp > 0] + n
        n += k
    assert _partitions_equal(seg, expected, ignore_zero=False)
    assert seg.max() == len(np.unique(seg))


def test_mws_clustering_near_uniform_weights_stress():
    """Regression: near-uniform affinity fields (e.g. an untrained net's
    sigmoid outputs) drive dense interleaved merge/constraint sequences;
    the native constraint rewiring once swapped the two roots' sets,
    breaking back-pointer symmetry until a root's set contained itself and
    erase-during-iteration segfaulted.  Must complete and match the pure
    python reference partition."""
    from cluster_tools_tpu import native
    from cluster_tools_tpu.ops.mws import grid_graph_edges

    rng = np.random.RandomState(7)
    affs = (0.5 + 0.06 * rng.randn(len(OFFSETS), 12, 32, 32)).astype(
        "float32").clip(0, 1)
    uva, wa, uvm, wm = grid_graph_edges(affs, OFFSETS)
    n = int(np.prod(affs.shape[1:]))
    fast = native.mutex_clustering(n, uva, wa, uvm, wm)
    assert len(fast) == n
    ref = native._py_mws(n, np.asarray(uva, "int64").reshape(-1, 2), wa,
                         np.asarray(uvm, "int64").reshape(-1, 2), wm)
    pairs = np.unique(np.stack([ref, fast]), axis=1)
    assert len(np.unique(pairs[0])) == pairs.shape[1]
    assert len(np.unique(pairs[1])) == pairs.shape[1]


def test_grid_graph_edges_host_matches_device():
    """impl='host' and impl='device' extraction must agree on the full
    edge sets (ids, weights, stride subsampling, mask handling) — the
    auto rule swaps them transparently, so divergence would change
    partitions between runs."""
    from cluster_tools_tpu.ops.mws import grid_graph_edges

    gt = _make_gt((10, 14, 14), seed=5)
    affs = _affs_from_gt(gt, OFFSETS, lo=0.1, hi=0.9)
    mask = np.zeros(gt.shape, np.uint8)  # non-bool on purpose
    mask[1:9, 2:13, 1:12] = 1
    kwargs = dict(strides=[2, 2, 2], mask=mask)
    host = grid_graph_edges(affs, OFFSETS, impl="host", **kwargs)
    dev = grid_graph_edges(affs, OFFSETS, impl="device", **kwargs)
    for h, d in zip(host, dev):
        np.testing.assert_array_equal(np.asarray(h, "float64"),
                                      np.asarray(d, "float64"))


def test_device_sorted_mws_matches_host():
    """The device extract+sort path (mutex_clustering_packed over the
    pre-sorted stream) must reproduce the host path's partition exactly
    (same priorities, same tie order, same zero-affinity drops)."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.mws import (
        _sorted_edges_resident, mutex_watershed_finalize_sorted,
        mutex_watershed_segmentation)

    gt = _make_gt((14, 18, 18), seed=5)
    affs = _affs_from_gt(gt, OFFSETS)
    host = mutex_watershed_segmentation(affs, OFFSETS)

    handles = _sorted_edges_resident(
        jnp.asarray(affs), (0, 0, 0), affs.shape[1:], OFFSETS, (1, 1, 1))
    dev, asum = mutex_watershed_finalize_sorted(
        handles[:2], affs.shape[1:], asum=handles[2])
    assert asum > 0
    assert _partitions_equal(host, dev, ignore_zero=False)


def test_device_sorted_mws_seeded():
    """Seeded variant: intra-seed edges boosted above every data weight,
    matching the host seeded path's partition."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.mws import (
        _sorted_edges_resident, mutex_watershed_finalize_sorted,
        mutex_watershed_segmentation)

    gt = _make_gt((12, 16, 16), seed=7)
    affs = _affs_from_gt(gt, OFFSETS)
    seeds = np.zeros(affs.shape[1:], "int32")
    seeds[:3] = gt[:3]  # pass-1 style seed plane
    host = mutex_watershed_segmentation(affs, OFFSETS, seeds=seeds)

    handles = _sorted_edges_resident(
        jnp.asarray(affs), (0, 0, 0), affs.shape[1:], OFFSETS, (1, 1, 1),
        seeds=seeds)
    dev, _ = mutex_watershed_finalize_sorted(
        handles[:2], affs.shape[1:], asum=handles[2])
    assert _partitions_equal(host, dev, ignore_zero=False)
