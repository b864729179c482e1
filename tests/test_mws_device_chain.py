"""Two-pass mutex watershed on the device path (``target="tpu"``): the
sorted-stream host scan against two independent scans, the whole chain
against the benchmark's plain reference, the overlapped block scans
against a serial drain, one compiled program for both passes, and a
resident volume that never outlives its run.

Small seeded sizes on the CPU; ``impl`` ``device`` is forced through the
task configs (the CPU backend would otherwise take the host path)."""

import os
import sys

import numpy as np
import pytest

from cluster_tools_tpu.core.config import ConfigDir
from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build
from cluster_tools_tpu.models.unet import DEFAULT_OFFSETS
from cluster_tools_tpu.workflows import mutex_watershed as mw

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from refs import mws_two_pass as ref  # noqa: E402

SHAPE = (24, 96, 96)      # 2 x 2 x 2 blocks
BLOCK = [12, 48, 48]
HALO = [2, 8, 8]


def seeded_affs(shape, seed, kind):
    """uint8 affinities of DEFAULT_OFFSETS: the benchmark's generated
    cells and ridges (``cells``), or uniform noise, whose mutex edges
    conflict everywhere (``noise``)."""
    if kind == "cells":
        import affinities
        import worley

        return affinities.generate(shape, seed, worley.load_mix("clean"),
                                   DEFAULT_OFFSETS)
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (len(DEFAULT_OFFSETS),) + shape,
                        dtype=np.uint8)


def device_stream(affs, seeds=None):
    """The program's sorted edge stream of one window as the host scan
    takes it: (u, v_packed)."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.mws import _sorted_edges_resident

    vol = jnp.asarray(mw.normalize(affs))
    u, vp, _ = _sorted_edges_resident(vol, (0, 0, 0), affs.shape[1:],
                                      DEFAULT_OFFSETS, (1, 1, 1), seeds)
    return np.asarray(u), np.asarray(vp)


@pytest.mark.parametrize("kind,seed,seeded", [
    ("noise", 1, False), ("noise", 2, True), ("cells", 3, False),
    ("cells", 4, True)])
def test_sorted_scan_matches_python_and_reference_scans(kind, seed, seeded,
                                                        monkeypatch):
    """Same labels as the pure-python scan (a hash set per cluster, every
    partner rewired on each merge) and as the reference's C++, on streams
    where most edges are mutex edges between live clusters."""
    from cluster_tools_tpu import native

    shape = (6, 30, 30) if kind == "noise" else (10, 40, 40)
    affs = seeded_affs(shape, seed, kind)
    seeds = None
    if seeded:
        seeds = np.zeros(shape, np.int64)
        seeds[:2] = 1 + np.arange(shape[2]) // 7   # pass-1 style seed plane
    u, vp = device_stream(affs, seeds)
    n = int(np.prod(shape))
    assert native.have_native()
    got = native.mutex_clustering_packed(n, u, vp)
    # the reference numbers clusters 1.. by first voxel, the scan 0..
    want = ref.mws(affs, DEFAULT_OFFSETS, seeds).ravel()
    np.testing.assert_array_equal(got.astype(np.int64) + 1, want)
    # the pure-python fallback, which calls _py_mws on the decoded stream
    monkeypatch.setattr(native, "_load", lambda: None)
    py = native.mutex_clustering_packed(n, u, vp)
    assert ref.mismatch(want, py) == (0, 0)
    kept = (u >= 0) & ((vp >> 29) & 1 == 0)
    mutex = (vp >> 30) & 1 != 0
    assert int((kept & mutex).sum()) > int((kept & ~mutex).sum())


def write_input(path, affs):
    with file_reader(path) as f:
        ds = f.require_dataset("affs", shape=affs.shape,
                               chunks=(1,) + tuple(BLOCK), dtype="uint8")
        ds[...] = affs


def run_chain(workdir, input_path, out_key="mws"):
    """One TwoPassMwsWorkflow(target="tpu") chain on the device path;
    returns its labels and its tmp folder."""
    config_dir = os.path.join(workdir, "configs")
    cd = ConfigDir(config_dir)
    cd.write_global_config({"block_shape": BLOCK, "max_num_retries": 0})
    for task in ("mws_pass1", "mws_pass2"):
        cd.write_task_config(task, {"impl": "device"})
    tmp = os.path.join(workdir, "tmp")
    out = os.path.join(workdir, "out.n5")
    wf = mw.TwoPassMwsWorkflow(
        input_path=input_path, input_key="affs", output_path=out,
        output_key=out_key, offsets=[list(o) for o in DEFAULT_OFFSETS],
        halo=HALO, tmp_folder=tmp, config_dir=config_dir, max_jobs=4,
        target="tpu")
    assert build([wf], raise_on_failure=True)
    with file_reader(out, "r") as f:
        return f[out_key][...], tmp


@pytest.fixture(scope="module")
def cells_input(tmp_path_factory):
    affs = seeded_affs(SHAPE, 2 ** 33 + 5, "cells")
    path = str(tmp_path_factory.mktemp("mws_in") / "in.n5")
    write_input(path, affs)
    return path, affs


def test_tpu_chain_matches_the_reference(tmp_path, cells_input):
    path, affs = cells_input
    seg, tmp = run_chain(str(tmp_path), path)
    want = ref.two_pass(affs, DEFAULT_OFFSETS, BLOCK, HALO)
    assert ref.mismatch(want, seg) == (0, 0)
    # every block a job of its pass, and every pass-2 block stitched
    pairs = [n for n in os.listdir(tmp)
             if n.startswith("mws_two_pass_assignments_block_")]
    assert len(pairs) == 4
    # a block's label that crosses a seam reaches the next block
    assert len(np.intersect1d(np.unique(seg[:12]), np.unique(seg[12:]))) > 0
    assert not mw._AFFS_DEV_CACHE


def test_threaded_and_serial_drains_are_byte_identical(tmp_path, cells_input,
                                                       monkeypatch):
    path, _ = cells_input
    runs = {}
    for workers in (1, 4):
        monkeypatch.setattr(mw, "_scan_workers", lambda *a, w=workers: w)
        seg, tmp = run_chain(str(tmp_path / f"w{workers}"), path)
        files = sorted(n for n in os.listdir(tmp)
                       if n.startswith("mws_two_pass_assignments_block_"))
        runs[workers] = (seg, {n: np.load(os.path.join(tmp, n))
                               for n in files})
    (seg1, pairs1), (seg4, pairs4) = runs[1], runs[4]
    assert seg1.dtype == seg4.dtype and seg1.tobytes() == seg4.tobytes()
    assert pairs1.keys() == pairs4.keys() and len(pairs1) == 4
    for name in pairs1:
        assert pairs1[name].tobytes() == pairs4[name].tobytes()


def test_both_passes_run_one_compiled_program(tmp_path, cells_input):
    from cluster_tools_tpu.ops.mws import _sorted_edges_resident_impl

    path, _ = cells_input
    _sorted_edges_resident_impl.clear_cache()
    run_chain(str(tmp_path), path)
    # eight blocks, one outer shape, seeded and unseeded alike
    assert _sorted_edges_resident_impl._cache_size() == 1


def test_rewritten_input_is_not_served_stale(tmp_path):
    """Two runs on one input path: the second reads the rewritten bytes,
    and no run leaves its volume resident."""
    path = str(tmp_path / "in.n5")
    first = seeded_affs(SHAPE, 7, "cells")
    second = seeded_affs(SHAPE, 8, "cells")
    write_input(path, first)
    seg1, _ = run_chain(str(tmp_path / "a"), path)
    assert not mw._AFFS_DEV_CACHE
    write_input(path, second)
    seg2, _ = run_chain(str(tmp_path / "b"), path)
    assert not mw._AFFS_DEV_CACHE
    assert ref.mismatch(ref.two_pass(first, DEFAULT_OFFSETS, BLOCK, HALO),
                        seg1) == (0, 0)
    assert ref.mismatch(ref.two_pass(second, DEFAULT_OFFSETS, BLOCK, HALO),
                        seg2) == (0, 0)


def test_scan_workers_follow_cores_and_memory(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(13)),
                        raising=False)
    monkeypatch.setattr(mw, "_host_available_bytes", lambda: 64 << 30)
    assert mw._scan_workers(4, 16_000_000, 190_000_000) == 4
    assert mw._scan_workers(40, 1000, 12000) == 12       # cores less one
    monkeypatch.setattr(mw, "_host_available_bytes", lambda: 12 << 30)
    assert mw._scan_workers(4, 16_000_000, 190_000_000) == 2
    monkeypatch.setattr(mw, "_host_available_bytes", lambda: 6 << 30)
    assert mw._scan_workers(4, 16_000_000, 190_000_000) == 1
    monkeypatch.setattr(mw, "_host_available_bytes", lambda: 0)
    assert mw._scan_workers(4, 16_000_000, 190_000_000) == 1
