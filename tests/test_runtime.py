"""Runtime tests: job protocol, executors, retry (fault injection).

Ports the reference's test strategy (SURVEY.md §4): the real task machinery is
exercised end-to-end with the local executor as the fake cluster, and a
deterministic FailingTask fixture (reference: test/retry/failing_task.py)
validates block-granular retry.
"""

import os

import numpy as np
import pytest

from cluster_tools_tpu.core import runtime
from cluster_tools_tpu.core.blocking import Blocking
from cluster_tools_tpu.core.config import ConfigDir
from cluster_tools_tpu.core.runtime import BlockTask, FailedJobsError
from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import DummyTask, FileTarget, Task, build


class FillTask(BlockTask):
    """Write block_id+1 into every voxel of each block."""

    task_name = "fill"

    def __init__(self, output_path, output_key, shape, **kw):
        self.output_path = output_path
        self.output_key = output_key
        self.shape = shape
        super().__init__(**kw)

    def run_impl(self):
        block_shape = self.global_block_shape()[: len(self.shape)]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=self.shape,
                              chunks=block_shape, dtype="uint32")
        block_list = self.blocks_in_volume(self.shape, block_shape)
        self.run_jobs(block_list, {
            "output_path": self.output_path, "output_key": self.output_key,
            "shape": list(self.shape), "block_shape": block_shape,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id, job_config, log_fn):
        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        with file_reader(cfg["output_path"]) as f:
            ds = f[cfg["output_key"]]
            for block_id in job_config["block_list"]:
                block = blocking.get_block(block_id)
                ds[block.bb] = np.full(block.shape, block_id + 1, dtype="uint32")
                log_fn(f"processed block {block_id}")


class FailingTask(FillTask):
    """Deterministically fail odd blocks on first attempt (reference:
    test/retry/failing_task.py:74-77), succeed on retry."""

    task_name = "failing"

    @classmethod
    def process_job(cls, job_id, job_config, log_fn):
        cfg = job_config["config"]
        marker_dir = cfg["marker_dir"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        with file_reader(cfg["output_path"]) as f:
            ds = f[cfg["output_key"]]
            for block_id in job_config["block_list"]:
                marker = os.path.join(marker_dir, f"attempted_{block_id}")
                if block_id % 2 == 1 and not os.path.exists(marker):
                    open(marker, "w").close()
                    raise RuntimeError(f"injected failure for block {block_id}")
                block = blocking.get_block(block_id)
                ds[block.bb] = np.full(block.shape, block_id + 1, dtype="uint32")
                log_fn(f"processed block {block_id}")


@pytest.mark.parametrize("target", ["local", "threads", "inline"])
def test_fill_task_all_executors(tmp_workdir, tmp_path, target):
    tmp_folder, config_dir = tmp_workdir
    out = str(tmp_path / f"out_{target}.n5")
    task = FillTask(output_path=out, output_key="data", shape=(20, 20, 20),
                    tmp_folder=tmp_folder, config_dir=config_dir,
                    max_jobs=4, target=target)
    assert build([task])
    with file_reader(out, "r") as f:
        data = f["data"][:]
    blocking = Blocking([20, 20, 20], [10, 10, 10])
    for bid in range(blocking.n_blocks):
        assert (data[blocking.get_block(bid).bb] == bid + 1).all()
    assert task.complete()


def test_retry_fills_failed_blocks(tmp_workdir, tmp_path):
    tmp_folder, config_dir = tmp_workdir
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "max_num_retries": 2})
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir)
    out = str(tmp_path / "out.n5")
    task = FailingTask(output_path=out, output_key="data", shape=(20, 20, 20),
                       tmp_folder=tmp_folder, config_dir=config_dir,
                       max_jobs=4, target="local")
    task.task_config["marker_dir"] = marker_dir

    # marker_dir must reach the workers through the task-specific config
    orig = task.run_jobs

    def run_jobs(block_list, cfg, **kw):
        cfg = {**cfg, "marker_dir": marker_dir}
        return orig(block_list, cfg, **kw)

    task.run_jobs = run_jobs
    assert build([task])
    with file_reader(out, "r") as f:
        data = f["data"][:]
    blocking = Blocking([20, 20, 20], [10, 10, 10])
    for bid in range(blocking.n_blocks):
        assert (data[blocking.get_block(bid).bb] == bid + 1).all(), bid


def test_no_retry_raises(tmp_workdir, tmp_path):
    tmp_folder, config_dir = tmp_workdir  # max_num_retries = 0
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir)
    out = str(tmp_path / "out.n5")
    task = FailingTask(output_path=out, output_key="data", shape=(20, 20, 20),
                       tmp_folder=tmp_folder, config_dir=config_dir,
                       max_jobs=1, target="local")
    orig = task.run_jobs

    def run_jobs(block_list, cfg, **kw):
        return orig(block_list, {**cfg, "marker_dir": marker_dir}, **kw)

    task.run_jobs = run_jobs
    assert not build([task])
    with pytest.raises(FailedJobsError):
        task.run_impl()
    # failed logs renamed -> target invalid -> task not complete
    assert not task.complete()


def test_workflow_resume_skips_complete(tmp_workdir, tmp_path):
    tmp_folder, config_dir = tmp_workdir
    out = str(tmp_path / "out.n5")
    runs = []

    class Recording(FillTask):
        task_name = "recording"

        def run_impl(self):
            runs.append(1)
            super().run_impl()

    t = Recording(output_path=out, output_key="d", shape=(10, 10, 10),
                  tmp_folder=tmp_folder, config_dir=config_dir,
                  max_jobs=1, target="inline")
    assert build([t])
    assert build([Recording(output_path=out, output_key="d", shape=(10, 10, 10),
                            tmp_folder=tmp_folder, config_dir=config_dir,
                            max_jobs=1, target="inline")])
    assert len(runs) == 1  # second build skipped the complete task


def test_dependency_chain_order(tmp_workdir):
    tmp_folder, config_dir = tmp_workdir
    order = []

    class T(Task):
        def __init__(self, name, dep=None):
            self.name, self.dep = name, dep
            super().__init__()
            self._done = False

        def requires(self):
            return self.dep

        def output(self):
            class _T:
                def exists(s):
                    return self._done
            _t = _T()
            _t.path = self.name
            return _t

        @property
        def task_id(self):
            return self.name

        def run(self):
            order.append(self.name)
            self._done = True

    a = T("a")
    b = T("b", a)
    c = T("c", b)
    assert build([c])
    assert order == ["a", "b", "c"]


def test_log_parsing_helpers(tmp_path):
    lp = str(tmp_path / "x.log")
    with open(lp, "w") as f:
        f.write("2026-01-01T00:00:00.000000: processed block 3\n")
        f.write("2026-01-01T00:00:05.000000: processed block 7\n")
        f.write("2026-01-01T00:00:09.000000: processed job 0\n")
    assert runtime.parse_job_success(lp, 0)
    assert not runtime.parse_job_success(lp, 1)
    assert runtime.parse_processed_blocks(lp) == {3, 7}
    rt = runtime.parse_job_runtime(lp)
    assert rt is not None and abs(rt - 9.0) < 1.0


def test_bounded_pool_inline_and_threaded():
    """BoundedPool(0) runs inline (sequential reference mode); a threaded
    pool completes everything by close() and bounds in-flight futures."""
    from cluster_tools_tpu.core.runtime import BoundedPool

    done = []
    with BoundedPool(0) as pool:
        pool.submit(done.append, 1)
        assert done == [1]  # synchronous: visible immediately

    results = []
    with BoundedPool(2, max_inflight=3) as pool:
        for i in range(20):
            pool.submit(results.append, i)
            assert len(pool._pending) <= 3
    assert sorted(results) == list(range(20))


def test_stage_accumulator_thread_safety():
    """Regression (ISSUE 15 satellite): the global stage accumulator
    must hold up under 8 concurrent BoundedPool-style writers.  Unit
    additions (1.0 / 1 / one byte) make the expected totals EXACT — a
    lost read-modify-write shows up as a missing integer, not float
    noise."""
    import threading

    from cluster_tools_tpu.core.runtime import (BoundedPool, stage,
                                                stage_add, stage_bytes)

    n_threads, n_iter = 8, 500
    st0 = runtime.stages_snapshot()
    cn0 = runtime.counts_snapshot()
    by0 = runtime.bytes_snapshot()
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()      # maximize interleaving
        for _ in range(n_iter):
            stage_add("host-map", 1.0)
            stage_bytes("host-map", 1)
            with stage("host-scan"):
                pass

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert runtime.stages_delta(st0)["host-map"] == float(total)
    cn = runtime.counts_delta(cn0)
    assert cn["host-map"] == total and cn["host-scan"] == total
    assert runtime.bytes_delta(by0)["host-map"] == float(total)

    # same guarantee through the pool the drains actually use
    cn1 = runtime.counts_snapshot()
    with BoundedPool(n_threads) as pool:
        for _ in range(total):
            pool.submit(stage_add, "host-reduce", 1.0)
    assert runtime.counts_delta(cn1)["host-reduce"] == total


def test_bounded_pool_surfaces_worker_errors():
    from cluster_tools_tpu.core.runtime import BoundedPool

    def boom():
        raise RuntimeError("worker failed")

    with pytest.raises(RuntimeError, match="worker failed"):
        with BoundedPool(1) as pool:
            pool.submit(boom)

    # inline mode raises at the submit itself
    pool = BoundedPool(0)
    with pytest.raises(RuntimeError, match="worker failed"):
        pool.submit(boom)


# ---------------------------------------------------------------------------
# persistent executable cache (disk tier): serialize -> deserialize ->
# execute round trip, corruption safety, LRU bound, disk clear, and the
# exec_cache telemetry in task status JSONs.  All tests compile TRIVIAL
# jitted programs (sub-second) — the big resident programs are covered by
# the warm bench (BENCH_warm.json) and the slow server test.
# ---------------------------------------------------------------------------


@pytest.fixture()
def exec_disk(tmp_path):
    """Fresh, isolated disk tier; the session's warm in-memory executables
    are saved and restored so this fixture never forces later tests to
    recompile their (expensive) resident programs."""
    saved_cache = dict(runtime._EXEC_CACHE)
    saved_stats = dict(runtime.EXEC_CACHE_STATS)
    runtime._EXEC_CACHE.clear()
    runtime.exec_cache_clear()
    d = str(tmp_path / "exec_cache")
    runtime.exec_cache_configure(d)
    yield d
    runtime.exec_cache_configure(None)
    runtime._EXEC_CACHE.clear()
    runtime._EXEC_CACHE.update(saved_cache)
    runtime.EXEC_CACHE_STATS.update(saved_stats)


def _trivial_compiled(mult: float = 3.0):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: x * mult).lower(jnp.ones((4,))).compile()


def _one_device_compiled(dev_index: int):
    """A one-device program committed to ``jax.devices()[dev_index]`` of
    the 8-device virtual mesh — on a multi-device host, the disk tier must
    load it back onto THAT device, not onto every device of the backend."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.ones((4,)), jax.devices()[dev_index])
    return jax.jit(lambda x: x * 3.0).lower(x).compile(), x


def _mesh_compiled():
    """A program sharded over 4 of the 8 virtual devices, mesh order
    reversed (the recorded device order must survive the round trip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4][::-1]), ("d",))
    x = jax.device_put(jnp.ones((8,)), NamedSharding(mesh, P("d")))
    return jax.jit(lambda x: x * 3.0).lower(x).compile(), x


@pytest.mark.parametrize("program", ["default", "device5", "mesh4"])
def test_exec_cache_disk_roundtrip(exec_disk, program):
    """Cold: compile + persist.  After a process-death-equivalent memory
    clear, the same key deserializes from disk (no recompile) and the
    loaded executable computes the same results on the devices it was
    compiled for."""
    import jax.numpy as jnp

    if program == "default":
        build, x = _trivial_compiled, jnp.ones((4,))
    else:
        compiled, x = (_one_device_compiled(5) if program == "device5"
                       else _mesh_compiled())
        build = lambda: compiled  # noqa: E731
    key = ("triv", program)
    runtime.compile_cached(key, build)
    assert runtime.EXEC_CACHE_STATS["compiles"] == 1
    assert runtime.EXEC_CACHE_STATS["disk_writes"] == 1
    assert len(os.listdir(exec_disk)) == 1

    runtime.exec_cache_clear()     # memory only: the blob survives
    assert len(os.listdir(exec_disk)) == 1
    ent = runtime.compile_cached(key, build)
    assert runtime.EXEC_CACHE_STATS["compiles"] == 0
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 1
    assert runtime.EXEC_CACHE_STATS["deserialize_s"] > 0
    out = ent(x)
    assert out.sharding.device_set == x.sharding.device_set
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full(x.shape, 3.0, "float32"))
    # memory hit on the NEXT lookup — disk is only the process boundary
    runtime.compile_cached(key, build)
    assert runtime.EXEC_CACHE_STATS["hits"] == 1


def test_exec_cache_corrupt_blob_recompiles(exec_disk):
    """A damaged blob must cost one recompile, never a crash; the bad
    file is dropped and replaced by the fresh executable."""
    key = ("triv", 3.0)
    runtime.compile_cached(key, _trivial_compiled)
    blob = [f for f in os.listdir(exec_disk) if f.endswith(".jexec")][0]
    with open(os.path.join(exec_disk, blob), "wb") as f:
        f.write(b"not an executable")
    runtime.exec_cache_clear()
    runtime.compile_cached(key, _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["compiles"] == 1
    assert runtime.EXEC_CACHE_STATS["disk_misses"] == 1
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 0
    # the recompile re-persisted a good blob
    runtime.exec_cache_clear()
    runtime.compile_cached(key, _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 1


def test_exec_cache_clear_disk(exec_disk):
    """exec_cache_clear(disk=True) purges the persisted tier AND resets
    the counters (satellite: the full cold-start reset)."""
    runtime.compile_cached(("a",), _trivial_compiled)
    runtime.compile_cached(("b",), lambda: _trivial_compiled(5.0))
    assert len(os.listdir(exec_disk)) == 2
    runtime.exec_cache_clear(disk=True)
    assert [f for f in os.listdir(exec_disk)
            if f.endswith(".jexec")] == []
    assert runtime.EXEC_CACHE_STATS["compiles"] == 0
    assert runtime.EXEC_CACHE_STATS["disk_writes"] == 0
    # cold again: both keys recompile
    runtime.compile_cached(("a",), _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["compiles"] == 1


def test_exec_cache_lru_eviction(exec_disk):
    """The disk tier is size-bounded: oldest-touched blobs evict first."""
    runtime.compile_cached(("a",), _trivial_compiled)
    blob = os.path.join(exec_disk, os.listdir(exec_disk)[0])
    one = os.path.getsize(blob)
    # bound holds ONE blob (plus slack): writing a second evicts the first
    runtime.exec_cache_configure(exec_disk, max_bytes=int(one * 1.5))
    os.utime(blob, (1, 1))    # force 'a' to be the LRU entry
    runtime.compile_cached(("b",), lambda: _trivial_compiled(5.0))
    assert runtime.EXEC_CACHE_STATS["disk_evictions"] == 1
    assert len([f for f in os.listdir(exec_disk)
                if f.endswith(".jexec")]) == 1
    # 'a' is gone: a fresh process would recompile it, 'b' still loads
    runtime.exec_cache_clear()
    runtime.compile_cached(("b",), lambda: _trivial_compiled(5.0))
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 1
    runtime.compile_cached(("a",), _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["compiles"] == 1


@pytest.mark.parametrize("change", ["toolchain", "source"])
def test_exec_cache_fingerprint_binds_toolchain(exec_disk, monkeypatch,
                                                change):
    """The digest covers (jax/jaxlib version, device topology, package
    source): a version bump or an edited program means the old blob is
    simply never found — a MISS, not a load of a stale executable."""
    key = ("triv", 3.0)
    runtime.compile_cached(key, _trivial_compiled)
    runtime.exec_cache_clear()
    if change == "toolchain":
        monkeypatch.setattr(runtime, "_exec_cache_fingerprint",
                            lambda: "jax-from-the-future")
    else:
        monkeypatch.setattr(runtime, "_package_source_digest",
                            lambda: "edited")
    runtime.compile_cached(key, _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 0
    assert runtime.EXEC_CACHE_STATS["compiles"] == 1


def test_status_records_exec_cache(tmp_workdir, tmp_path):
    """Every task status JSON carries the exec_cache delta next to
    stage_counts (empty for tasks that never touch the executor cache)."""
    import json

    tmp_folder, config_dir = tmp_workdir
    out = str(tmp_path / "out.n5")
    task = FillTask(output_path=out, output_key="data", shape=(20, 20, 20),
                    tmp_folder=tmp_folder, config_dir=config_dir,
                    max_jobs=2, target="inline")
    assert build([task])
    with open(task.output().path) as f:
        status = json.load(f)
    assert "exec_cache" in status
    assert status["exec_cache"] == {}


def test_global_config_activates_disk_tier(tmp_path):
    """Setting ``exec_cache_dir`` in the global config wires the disk
    tier at task construction — the workflow-level opt-in."""
    saved = dict(runtime._DISK_TIER)
    try:
        d = str(tmp_path / "cfg_cache")
        config_dir = str(tmp_path / "configs")
        ConfigDir(config_dir).write_global_config(
            {"block_shape": [10, 10, 10], "exec_cache_dir": d,
             "exec_cache_max_bytes": 123456})
        FillTask(output_path=str(tmp_path / "o.n5"), output_key="d",
                 shape=(10, 10, 10), tmp_folder=str(tmp_path / "tmp"),
                 config_dir=config_dir, max_jobs=1, target="inline")
        assert runtime._exec_cache_dir() == d
        assert runtime._exec_cache_max_bytes() == 123456
    finally:
        runtime._DISK_TIER.update(saved)


@pytest.mark.parametrize("env_set", [True, False])
def test_use_compile_cache_places_both_tiers(tmp_path, monkeypatch,
                                             env_set):
    """Both cache tiers share one directory: JAX_COMPILATION_CACHE_DIR
    when set, else the fixed <checkout>/.jax_cache.  Nothing is compiled
    here, so no file lands in the checkout."""
    import jax

    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    saved = dict(runtime._DISK_TIER)
    saved_jax = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert runtime._exec_cache_dir() == want
    finally:
        runtime._DISK_TIER.update(saved)
        jax.config.update("jax_compilation_cache_dir", saved_jax)


# ---------------------------------------------------------------------------
# live-buffer ledger (ISSUE 17 tentpole b): bytes pinned by warm caches,
# exported through metrics_families and status JSONs
# ---------------------------------------------------------------------------

def test_ledger_accounting_basics():
    runtime.ledger_clear()
    try:
        runtime.ledger_add("fragment_cache", 100, 1)
        runtime.ledger_add("fragment_cache", 50, 1)
        runtime.ledger_set("raw_cache", 2048, 1)
        snap = runtime.ledger_snapshot()
        assert snap["fragment_cache"] == {"bytes": 150, "entries": 2}
        assert snap["raw_cache"] == {"bytes": 2048, "entries": 1}
        # releases clamp at zero — an over-release is a bookkeeping bug,
        # not a reason to report negative resident bytes
        runtime.ledger_add("fragment_cache", -500, -5)
        assert runtime.ledger_snapshot()["fragment_cache"] \
            == {"bytes": 0, "entries": 0}
        # snapshots are copies: mutating one never corrupts the ledger
        snap["raw_cache"]["bytes"] = -1
        assert runtime.ledger_snapshot()["raw_cache"]["bytes"] == 2048
        runtime.ledger_clear("raw_cache")
        assert "raw_cache" not in runtime.ledger_snapshot()
    finally:
        runtime.ledger_clear()


def test_ledger_metrics_families():
    runtime.ledger_clear()
    try:
        runtime.ledger_set("exec_cache", 4096, 2)
        fams = {f[0]: f for f in runtime.metrics_families()}
        assert fams["ctt_ledger_bytes"][3] \
            == [({"account": "exec_cache"}, 4096)]
        assert fams["ctt_ledger_entries"][3] \
            == [({"account": "exec_cache"}, 2)]
        runtime.ledger_clear()
        fams = {f[0]: f for f in runtime.metrics_families()}
        assert fams["ctt_ledger_bytes"][3] == [(None, 0)]
    finally:
        runtime.ledger_clear()


def test_exec_cache_ledger_tracks_blob_bytes(exec_disk):
    """compile_cached accounts the serialized blob's size under the
    'exec_cache' ledger account — on the build path AND the disk-hit
    path — and exec_cache_clear releases it."""
    runtime.ledger_clear()
    runtime.compile_cached(("triv", 3.0), _trivial_compiled)
    blob = [f for f in os.listdir(exec_disk) if f.endswith(".jexec")][0]
    nbytes = os.path.getsize(os.path.join(exec_disk, blob))
    assert nbytes > 0
    led = runtime.ledger_snapshot()["exec_cache"]
    assert led == {"bytes": nbytes, "entries": 1}
    runtime.exec_cache_clear()
    assert "exec_cache" not in runtime.ledger_snapshot()
    # warm re-load from disk re-pins the same footprint
    runtime.compile_cached(("triv", 3.0), _trivial_compiled)
    assert runtime.EXEC_CACHE_STATS["disk_hits"] == 1
    assert runtime.ledger_snapshot()["exec_cache"] \
        == {"bytes": nbytes, "entries": 1}


def test_fragment_cache_puts_feed_ledger():
    """The fused pipeline's cache-put helpers keep the ledger in sync,
    overwrites included, and clear_caches releases everything."""
    from cluster_tools_tpu.workflows import fused_pipeline as fp

    fp.clear_caches()
    try:
        fp._fragment_cache_put(("p", "k", 0), np.zeros(10, "uint16"),
                               0, ((0, 1),))
        fp._fragment_cache_put(("p", "k", 1), np.zeros(5, "uint16"),
                               0, ((0, 1),))
        fp._raw_cache_put(("p", "k"), np.zeros(7, "uint8"), False)
        snap = runtime.ledger_snapshot()
        assert snap["fragment_cache"] == {"bytes": 30, "entries": 2}
        assert snap["raw_cache"] == {"bytes": 7, "entries": 1}
        # overwriting a key releases the old entry's bytes first
        fp._fragment_cache_put(("p", "k", 0), np.zeros(20, "uint16"),
                               0, ((0, 1),))
        assert runtime.ledger_snapshot()["fragment_cache"] \
            == {"bytes": 50, "entries": 2}
        assert fp._FRAGMENT_CACHE[("p", "k", 0)][0].nbytes == 40
        fp.clear_caches()
        snap = runtime.ledger_snapshot()
        assert "fragment_cache" not in snap and "raw_cache" not in snap
    finally:
        fp.clear_caches()
