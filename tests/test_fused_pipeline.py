"""Fused per-block chain vs the classic task split: same problem, same
segmentation."""

import numpy as np
import pytest

from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build


def _instance(shape=(32, 48, 48), n_cells=10, seed=0):
    from scipy import ndimage

    rng = np.random.RandomState(seed)
    pts = rng.rand(n_cells, 3) * np.array(shape)
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    coords = np.stack([g.ravel() for g in grids], 1).astype("float32")
    d = np.linalg.norm(coords[:, None, :] - pts[None], axis=2)
    d.sort(axis=1)
    bnd = np.exp(-(d[:, 1] - d[:, 0]) ** 2 / 4.0).reshape(shape)
    return ndimage.gaussian_filter(bnd, 1.0).astype("float32")


def _partition_bijection(a, b):
    """True when two labelings describe the same partition."""
    pairs = np.unique(np.stack([a.ravel(), b.ravel()], 1), axis=0)
    return (len(np.unique(pairs[:, 0])) == len(pairs)
            and len(np.unique(pairs[:, 1])) == len(pairs))


@pytest.mark.slow
def test_fused_matches_classic_chain(tmp_path, tmp_workdir):
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.graph import load_graph
    from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

    tmp_folder, config_dir = tmp_workdir
    # deliberately NOT divisible by the block shape: border blocks are
    # clipped, exercising the real-extent masking of the fused program
    shape = (34, 52, 48)
    bnd = _instance(shape)
    path = str(tmp_path / "d.n5")
    with file_reader(path) as f:
        ds = f.require_dataset("bmap", shape=shape, chunks=(16, 24, 24),
                               dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")

    ConfigDir(config_dir).write_global_config({"block_shape": [16, 24, 24]})
    for name in ("watershed", "fused_segmentation"):
        ConfigDir(config_dir).write_task_config(
            name, {"threshold": 0.4, "size_filter": 25})

    # classic: watershed workflow + problem + multicut
    ws = WatershedWorkflow(
        input_path=path, input_key="bmap", output_path=path,
        output_key="ws_classic", tmp_folder=f"{tmp_folder}_c",
        config_dir=config_dir, max_jobs=2, target="tpu")
    mc = ctt.MulticutSegmentationWorkflow(
        input_path=path, input_key="bmap", ws_path=path,
        ws_key="ws_classic", problem_path=str(tmp_path / "pc.n5"),
        output_path=path, output_key="seg_classic",
        tmp_folder=f"{tmp_folder}_c", config_dir=config_dir, max_jobs=2,
        target="tpu", n_scales=1, dependency=ws)
    assert build([mc], raise_on_failure=True)

    # fused: single workflow, fragments computed inside
    mf = ctt.MulticutSegmentationWorkflow(
        input_path=path, input_key="bmap", ws_path=path,
        ws_key="ws_fused", problem_path=str(tmp_path / "pf.n5"),
        output_path=path, output_key="seg_fused",
        tmp_folder=f"{tmp_folder}_f", config_dir=config_dir, max_jobs=2,
        target="tpu", n_scales=1, fused=True)
    assert build([mf], raise_on_failure=True)

    with file_reader(path, "r") as f:
        ws_c = f["ws_classic"][:]
        ws_f = f["ws_fused"][:]
        seg_c = f["seg_classic"][:]
        seg_f = f["seg_fused"][:]
        max_id = f["ws_fused"].attrs["maxId"]

    # identical fragment PARTITIONS (ids may be numbered differently)
    assert _partition_bijection(ws_c, ws_f)
    # fused ids are globally consecutive without a relabel pass
    u = np.unique(ws_f)
    assert u[0] >= 1 and u[-1] == len(u) == max_id

    # identical graphs up to the fragment renumbering: compare edge COUNTS
    # and the feature tables through the bijection
    _, e_c, _ = load_graph(str(tmp_path / "pc.n5"), "s0/graph")
    _, e_f, _ = load_graph(str(tmp_path / "pf.n5"), "s0/graph")
    assert len(e_c) == len(e_f)
    # map classic ids -> fused ids via voxel-wise correspondence
    lut = np.zeros(int(ws_c.max()) + 1, "uint64")
    lut[ws_c.ravel()] = ws_f.ravel()
    mapped = np.ascontiguousarray(np.stack(
        [np.minimum(lut[e_c[:, 0]], lut[e_c[:, 1]]),
         np.maximum(lut[e_c[:, 0]], lut[e_c[:, 1]])], 1)).view(
        [("u", "uint64"), ("v", "uint64")]).reshape(-1)
    e_f_packed = np.ascontiguousarray(e_f.astype("uint64")).view(
        [("u", "uint64"), ("v", "uint64")]).reshape(-1)
    np.testing.assert_array_equal(np.sort(mapped), e_f_packed)

    with file_reader(str(tmp_path / "pc.n5"), "r") as f:
        feats_c = f["features"][:]
    with file_reader(str(tmp_path / "pf.n5"), "r") as f:
        feats_f = f["features"][:]
    # row i of the classic table corresponds to the fused row of its
    # mapped edge (e_f is lex-sorted, so searchsorted locates it)
    order_map = np.searchsorted(e_f_packed, mapped)
    np.testing.assert_allclose(feats_f[order_map], feats_c, rtol=1e-4,
                               atol=1e-5)

    # the final segmentations agree (identical problems; id-renumbering
    # can flip solver tie-breaks on equal gains, so compare by Rand error
    # rather than demanding an exact bijection)
    from cluster_tools_tpu.utils.validation import rand_index

    are, _ = rand_index(seg_f, seg_c)
    assert are < 0.02, are


def test_fused_hybrid_ws_method(tmp_path, tmp_workdir):
    """ws_method='hybrid' (host C++ flood between two device stages)
    produces a valid consecutive fragmentation and a good segmentation."""
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu import native
    from cluster_tools_tpu.core.config import ConfigDir

    if not native.have_native():
        import pytest

        pytest.skip("native library unavailable")

    tmp_folder, config_dir = tmp_workdir
    shape = (32, 48, 48)
    bnd = _instance(shape)
    path = str(tmp_path / "d.n5")
    with file_reader(path) as f:
        ds = f.require_dataset("bmap", shape=shape, chunks=(16, 24, 24),
                               dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")

    ConfigDir(config_dir).write_global_config({"block_shape": [16, 24, 24]})
    ConfigDir(config_dir).write_task_config(
        "fused_segmentation",
        {"threshold": 0.4, "size_filter": 25, "ws_method": "hybrid"})

    mf = ctt.MulticutSegmentationWorkflow(
        input_path=path, input_key="bmap", ws_path=path,
        ws_key="ws_hybrid", problem_path=str(tmp_path / "ph.n5"),
        output_path=path, output_key="seg_hybrid",
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2,
        target="tpu", n_scales=1, fused=True)
    assert build([mf], raise_on_failure=True)

    with file_reader(path, "r") as f:
        ws = f["ws_hybrid"][:]
        seg = f["seg_hybrid"][:]
        max_id = f["ws_hybrid"].attrs["maxId"]
    assert (ws > 0).all()
    u = np.unique(ws)
    assert u[0] == 1 and u[-1] == len(u) == max_id
    # fragments respect the size filter
    _, counts = np.unique(ws, return_counts=True)
    assert counts.min() >= 5  # local refill keeps fragments reasonable
    assert len(np.unique(seg)) >= 2


#: the resident program's stage scopes, as the profiler trace reads them
RESIDENT_SCOPES = ("edt", "smooth", "seeds", "watershed", "relabel", "pairs",
                   "edge_stats", "rle")


def _op_name_scopes(compiled, program):
    """The second component of every ``op_name`` under ``jit(program)``
    in a compiled program's HLO metadata."""
    import re

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {n.split("/")[1] for n in names
            if n.startswith(f"jit({program})/") and n.count("/") >= 2}


def _resident(in_dtype):
    import jax
    import jax.numpy as jnp

    from cluster_tools_tpu.workflows.fused_pipeline import _resident_program

    prog = _resident_program((12, 20, 20), (2, 2, 2), in_dtype, 0.4, 2.0,
                             2.0, 0.8, 25, 4096, 1 << 12, 3, 1 << 14, 2)
    return prog.lower(jax.ShapeDtypeStruct((20, 36, 36), in_dtype),
                      jax.ShapeDtypeStruct((6,), jnp.int32)).compile(), \
        "run", RESIDENT_SCOPES


def _watershed_pipeline(in_dtype):
    import jax

    from cluster_tools_tpu.workflows.watershed import _ws_pipeline_3d

    prog = _ws_pipeline_3d(0.4, 2.0, 2.0, 0.8, 25, ws_method="coarse")
    return prog.lower(jax.ShapeDtypeStruct((12, 20, 20), in_dtype)
                      ).compile(), "pipeline", RESIDENT_SCOPES[:4]


@pytest.mark.parametrize("build_program, in_dtype", [
    (_resident, "uint8"), (_resident, "float32"),
    (_watershed_pipeline, "uint8")],
    ids=["resident-uint8", "resident-float32", "watershed-uint8"])
def test_device_programs_carry_stage_scopes(build_program, in_dtype):
    """Every stage scope of the resident program (both input paths), and
    the watershed stages of the classic pipeline it shares its core
    with, is an ``op_name`` prefix of the compiled program: the scope the
    profiler trace reports for each device op."""
    compiled, program, scopes = build_program(in_dtype)
    found = _op_name_scopes(compiled, program)
    assert set(scopes) <= found, sorted(found)
