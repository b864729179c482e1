"""Store writes timed as ``runtime.stage("store-write")`` blocks.

The blockwise writers (the final assignment write, the edit patcher's
block rewrite, the thresholded-components and fused device passes) time
each ``ds_out[bb] = ...`` as a stage block, which also shows it as a
``ctt.stage.store-write`` span in a profiler trace.  The stage must
record the seconds the write took: here every write advances a
per-thread clock by a fixed step, so each stage reads exactly that step,
whichever thread (writer pool or main) ran it.
"""

import threading
import time

import numpy as np
import pytest

from cluster_tools_tpu.core import runtime, storage
from cluster_tools_tpu.core.config import ConfigDir
from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build

WRITE_S = 0.5


class _WriteClock:
    """Stand-in for ``runtime.time``: ``perf_counter`` reads a per-thread
    clock that only a store write advances."""

    def __init__(self):
        self._tls = threading.local()

    def perf_counter(self):
        return getattr(self._tls, "t", 0.0)

    def advance(self, dt):
        self._tls.t = self.perf_counter() + dt

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture()
def write_clock(monkeypatch):
    clock = _WriteClock()
    write = storage.Dataset.__setitem__

    def timed_write(ds, bb, value):
        write(ds, bb, value)
        clock.advance(WRITE_S)

    monkeypatch.setattr(runtime, "time", clock)
    monkeypatch.setattr(storage.Dataset, "__setitem__", timed_write)
    return clock


def _volume(path, key, shape, dtype, seed=0):
    rng = np.random.RandomState(seed)
    with file_reader(path) as f:
        ds = f.require_dataset(key, shape=shape, chunks=(8, 8, 8),
                               dtype=dtype)
        if dtype == "uint64":
            ds[...] = rng.randint(1, 20, size=shape).astype(dtype)
        else:
            ds[...] = rng.rand(*shape).astype(dtype)


def _rewrite_blocks(tmp_path, config_dir, tmp_folder):
    from cluster_tools_tpu.workflows.write import rewrite_blocks

    path = str(tmp_path / "d.n5")
    _volume(path, "frag", (16, 16, 16), "uint64")
    with file_reader(path) as f:
        f.require_dataset("seg", shape=(16, 16, 16), chunks=(8, 8, 8),
                          dtype="uint64")
    return rewrite_blocks(path, "frag", path, "seg",
                          np.arange(20, dtype="uint64"), range(8), (8, 8, 8))


def _write_assignments(tmp_path, config_dir, tmp_folder):
    from cluster_tools_tpu.workflows.write import WriteAssignments

    path = str(tmp_path / "d.n5")
    _volume(path, "frag", (16, 16, 16), "uint64")
    table = str(tmp_path / "table.npy")
    np.save(table, np.arange(20, dtype="uint64"))
    assert build([WriteAssignments(
        input_path=path, input_key="frag", output_path=path,
        output_key="seg", assignment_path=table, tmp_folder=tmp_folder,
        config_dir=config_dir, max_jobs=1, target="inline")],
        raise_on_failure=True)
    return 8


def _thresholded_components(tmp_path, config_dir, tmp_folder):
    from cluster_tools_tpu.workflows.fused_pipeline import clear_caches
    from cluster_tools_tpu.workflows.thresholded_components import (
        ThresholdedComponentsWorkflow,
    )

    path = str(tmp_path / "d.n5")
    _volume(path, "raw", (16, 16, 16), "float32")
    clear_caches()
    assert build([ThresholdedComponentsWorkflow(
        input_path=path, input_key="raw", output_path=path,
        output_key="cc", threshold=0.5, tmp_folder=tmp_folder,
        config_dir=config_dir, max_jobs=1, target="tpu")],
        raise_on_failure=True)
    return None


def _fused_device(tmp_path, config_dir, tmp_folder):
    from cluster_tools_tpu.workflows.fused_pipeline import (
        FusedSegmentationBlocks,
    )

    path = str(tmp_path / "d.n5")
    _volume(path, "bmap", (16, 16, 16), "float32")
    ConfigDir(config_dir).write_task_config(
        "fused_segmentation", {"halo": [2, 2, 2], "threshold": 0.4})
    assert build([FusedSegmentationBlocks(
        input_path=path, input_key="bmap", output_path=path,
        output_key="ws", problem_path=str(tmp_path / "p.n5"),
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=1,
        target="tpu")], raise_on_failure=True)
    return 8


@pytest.mark.parametrize("site", [
    _rewrite_blocks, _write_assignments, _thresholded_components,
    _fused_device], ids=lambda f: f.__name__.lstrip("_"))
def test_store_write_stage_records_the_write_seconds(site, write_clock,
                                                      tmp_path,
                                                      monkeypatch):
    """Each ``store-write`` stage times exactly one store write: the
    stage's seconds are the writes' seconds, summed over its entries."""
    if site is _thresholded_components:
        monkeypatch.setenv("CTT_FORCE_RESIDENT", "1")  # the device pass
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "configs")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [8, 8, 8], "max_num_retries": 0})
    st0, cn0 = runtime.stages_snapshot(), runtime.counts_snapshot()
    n_blocks = site(tmp_path, config_dir, tmp_folder)
    n_writes = runtime.counts_delta(cn0)["store-write"]
    assert n_writes >= (n_blocks or 1)
    assert runtime.stages_delta(st0)["store-write"] == pytest.approx(
        WRITE_S * n_writes)
