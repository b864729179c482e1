"""Multi-host scaffolding: 2 cooperating processes complete a blockwise
workflow over the shared store (per-process block ownership, lead-only
global tasks, filesystem barriers)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from cluster_tools_tpu.core.storage import file_reader
from cluster_tools_tpu.core.workflow import build

DRIVER = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np

if __name__ == "__main__":
    from cluster_tools_tpu.core.workflow import build
    from cluster_tools_tpu.workflows.thresholded_components import (
        ThresholdedComponentsWorkflow)

    wf = ThresholdedComponentsWorkflow(
        input_path={path!r}, input_key="vol", output_path={path!r},
        output_key="cc_multi", threshold=0.5, tmp_folder={tmp!r},
        config_dir={cfg!r}, max_jobs=4, target="inline")
    assert build([wf], raise_on_failure=True)
"""


def _volume(shape=(16, 16, 32), seed=0):
    rng = np.random.RandomState(seed)
    vol = np.zeros(shape, "float32")
    zz, yy, xx = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    for _ in range(30):
        c = rng.rand(3) * np.array(shape)
        d2 = (zz - c[0]) ** 2 + (yy - c[1]) ** 2 + (xx - c[2]) ** 2
        vol = np.maximum(vol, np.exp(-d2 / 3.0).astype("float32"))
    return vol


def test_two_process_blockwise_cooperation(tmp_path, tmp_workdir):
    from cluster_tools_tpu.workflows.thresholded_components import (
        ThresholdedComponentsWorkflow)

    tmp_folder, config_dir = tmp_workdir
    vol = _volume()
    path = str(tmp_path / "d.n5")
    with file_reader(path) as f:
        ds = f.require_dataset("vol", shape=vol.shape, chunks=(8, 8, 8),
                               dtype="float32")
        ds[:] = vol

    # single-process reference result
    wf = ThresholdedComponentsWorkflow(
        input_path=path, input_key="vol", output_path=path,
        output_key="cc_single", threshold=0.5,
        tmp_folder=f"{tmp_folder}_single", config_dir=config_dir,
        max_jobs=2, target="inline")
    assert build([wf], raise_on_failure=True)

    # two cooperating processes, same driver script (SPMD style)
    script = str(tmp_path / "driver.py")
    multi_tmp = f"{tmp_folder}_multi"
    with open(script, "w") as f:
        f.write(DRIVER.format(repo=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), path=path, tmp=multi_tmp,
            cfg=config_dir))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CTT_PROCESS_COUNT"] = "2"
    procs = []
    for pid in range(2):
        e = dict(env)
        e["CTT_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]

    with file_reader(path, "r") as f:
        single = f["cc_single"][:]
        multi = f["cc_multi"][:]
    np.testing.assert_array_equal(multi, single)

    # both processes actually processed blocks (job 0 AND job 1 logs)
    logs = os.listdir(os.path.join(multi_tmp, "logs"))
    assert any(name.endswith("_0.log") for name in logs)
    assert any(name.endswith("_1.log") for name in logs)
    import re

    counts = []
    for job in (0, 1):
        blocks = 0
        for name in logs:
            if name == f"block_components_{job}.log":
                with open(os.path.join(multi_tmp, "logs", name)) as f:
                    blocks = len(re.findall("processed block", f.read()))
        counts.append(blocks)
    assert all(c > 0 for c in counts), counts


RETRY_DRIVER = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np

from cluster_tools_tpu.core.blocking import Blocking
from cluster_tools_tpu.core.runtime import BlockTask
from cluster_tools_tpu.core.storage import file_reader


class FlakyFillTask(BlockTask):
    '''Writes block_id+1 into each block; ODD blocks raise on the first
    attempt (marker files track attempts) — the multiprocess analog of the
    reference's FailingTask fixture (test/retry/failing_task.py).'''

    task_name = "flaky_fill"

    def __init__(self, path, **kw):
        self.path = path
        super().__init__(**kw)

    def run_impl(self):
        with file_reader(self.path, "r") as f:
            shape = list(f["vol"].shape)
        bs = self.global_block_shape()
        with file_reader(self.path) as f:
            f.require_dataset("filled", shape=shape, chunks=bs,
                              dtype="uint32")
        self.run_jobs(self.blocks_in_volume(shape, bs),
                      {{"path": self.path, "shape": shape,
                        "block_shape": bs,
                        "marker_dir": self.tmp_folder}})

    @classmethod
    def process_job(cls, job_id, job_config, log_fn):
        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        f = file_reader(cfg["path"])
        ds = f["filled"]
        injected = []
        for bid in job_config["block_list"]:
            marker = os.path.join(cfg["marker_dir"], f"attempt_{{bid}}")
            first = not os.path.exists(marker)
            open(marker, "a").close()
            if bid % 2 == 1 and first:
                injected.append(bid)  # skipped: no success line logged
                continue
            ds[blocking.get_block(bid).bb] = bid + 1
            log_fn(f"processed block {{bid}}")
        if injected:
            raise RuntimeError(f"injected failures for blocks {{injected}}")


if __name__ == "__main__":
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.workflow import build

    cfg = ConfigDir({cfg!r})
    cfg.write_global_config({{"block_shape": [8, 8, 8],
                              "max_num_retries": 1}})
    task = FlakyFillTask(path={path!r}, tmp_folder={tmp!r},
                         config_dir={cfg!r}, max_jobs=2, target="inline")
    assert build([task], raise_on_failure=True)
"""


def test_two_process_in_run_block_retry(tmp_path, tmp_workdir):
    """Injected per-block failures recover IN-RUN across two processes —
    no driver rerun (reference semantics cluster_tasks.py:136-170)."""
    tmp_folder, config_dir = tmp_workdir
    path = str(tmp_path / "d.n5")
    shape = (16, 16, 16)  # 8 blocks of [8,8,8]
    with file_reader(path) as f:
        ds = f.require_dataset("vol", shape=shape, chunks=(8, 8, 8),
                               dtype="float32")
        ds[:] = 0.0

    script = str(tmp_path / "driver.py")
    multi_tmp = f"{tmp_folder}_retry"
    with open(script, "w") as f:
        f.write(RETRY_DRIVER.format(
            repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            path=path, tmp=multi_tmp, cfg=config_dir))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CTT_PROCESS_COUNT"] = "2"
    procs = []
    for pid in range(2):
        e = dict(env)
        e["CTT_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]

    from cluster_tools_tpu.core.blocking import Blocking

    with file_reader(path, "r") as f:
        filled = f["filled"][:]
    blocking = Blocking(list(shape), [8, 8, 8])
    for bid in range(8):
        bb = blocking.get_block(bid).bb
        assert (filled[bb] == bid + 1).all(), f"block {bid} missing"
    # every block attempted; the in-run retry really fired (a retry log
    # line exists and the task was built by a SINGLE driver invocation)
    assert all(os.path.exists(os.path.join(multi_tmp, f"attempt_{b}"))
               for b in range(8))
    assert any("multiprocess retry" in o for o in outs), outs[0][-500:]


COLLECTIVE_DRIVER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {repo!r})

if __name__ == "__main__":
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from cluster_tools_tpu.parallel.multihost import (init_distributed,
                                                      make_multihost_mesh)

    from jax import shard_map

    pid = int(sys.argv[1])
    init_distributed(coordinator_address="localhost:{port}",
                     num_processes=2, process_id=pid)
    assert jax.process_count() == 2
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_multihost_mesh(("data", "model"), dcn_axis=0)
    assert mesh.devices.shape == (2, 4), mesh.devices.shape
    # the data axis spans BOTH processes: a psum over it is a real
    # cross-process collective (gloo transport on CPU)
    owners = np.vectorize(lambda d: d.process_index)(mesh.devices)
    assert set(owners[:, 0]) == {{0, 1}}, owners

    f = jax.jit(shard_map(lambda a: jax.lax.psum(a, "data"),
                          mesh=mesh, in_specs=P("data"),
                          out_specs=P()))
    x = jnp.arange(8.0)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    r = np.asarray(f(xs))
    np.testing.assert_allclose(r, np.arange(8.0).reshape(2, 4).sum(0))
    print(f"p{{pid}} cross-process psum ok: {{r.tolist()}}")
"""


def test_two_process_cross_process_psum(tmp_path):
    """REAL cross-process collective: 2 jax.distributed CPU processes x 4
    virtual devices, one mesh from make_multihost_mesh, one psum over the
    process-spanning axis (the pod-scale path, gloo instead of DCN)."""
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    script = str(tmp_path / "collective_driver.py")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(script, "w") as f:
        f.write(COLLECTIVE_DRIVER.format(repo=repo, port=port))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "CTT_PROCESS_COUNT", "CTT_PROCESS_ID",
                        "PYTHONPATH")}
    procs = [subprocess.Popen([sys.executable, script, str(pid)], env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for pid in range(2)]
    outs = [p.communicate(timeout=300)[0].decode() for p in procs]
    if any("Multiprocess computations aren't implemented" in o
           for o in outs):
        # this jaxlib's CPU backend has no cross-process collectives
        # (gloo-less build) — the path is exercised on real multihost
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    assert all("cross-process psum ok" in o for o in outs), outs[0][-500:]


SHARD_DRIVER = """
import json, os, sys, time
sys.path.insert(0, {repo!r})

if __name__ == "__main__":
    from cluster_tools_tpu.core import telemetry
    from cluster_tools_tpu.parallel import multihost as mh

    pid = mh.process_index()
    telemetry.configure(enabled=True)
    with telemetry.span(f"job:p{{pid}}", cat="job", process_index=pid,
                        process_count=mh.process_count()):
        with telemetry.span("sync-execute", cat="stage") as sp:
            time.sleep(0.05 * (pid + 1))
            telemetry.annotate_memory(sp)
    anchor = mh.clock_anchor({tmp!r})
    mh.export_trace_shard({tmp!r}, anchor=anchor)
    mh.fs_barrier({tmp!r}, "shards-done")
    if mh.is_lead():
        m = mh.merge_trace_shards(
            {tmp!r}, os.path.join({tmp!r}, "merged_trace.json"))
        with open(os.path.join({tmp!r}, "merge_summary.json"), "w") as f:
            json.dump(m, f)
    print("shard ok")
"""


def test_two_process_trace_shards_merge(tmp_path):
    """ISSUE 17 acceptance: a 2-process run exports per-process trace
    shards (barrier-aligned clock anchors), and the lead merges them
    into ONE Perfetto-loadable trace whose rollups cross-check the
    per-process span counts."""
    import json

    tmp = str(tmp_path / "shared")
    os.makedirs(tmp)
    script = str(tmp_path / "driver.py")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(script, "w") as f:
        f.write(SHARD_DRIVER.format(repo=repo, tmp=tmp))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CTT_PROCESS_COUNT"] = "2"
    procs = []
    for pid in range(2):
        e = dict(env)
        e["CTT_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]

    # shards are self-describing (satellite: process identity)
    for pid in range(2):
        with open(os.path.join(tmp, f"trace_shard_p{pid}.json")) as f:
            sh = json.load(f)
        assert sh["process_index"] == pid
        assert sh["process_count"] == 2
        assert sh["spans"], sh

    with open(os.path.join(tmp, "merge_summary.json")) as f:
        m = json.load(f)
    assert m["n_processes"] == 2
    assert [p["pid"] for p in m["processes"]] == [1, 2]
    # job, stage and memory-sample spans of each process
    assert [p["n_spans"] for p in m["processes"]] == [3, 3]
    # merged rollup aggregates stage seconds across the mesh: the two
    # processes' sync-execute sleeps of 0.05 and 0.10 s
    assert m["rollups"]["stage_seconds"]["sync-execute"] >= 0.14
    assert m["rollups"]["memory"]["peak_host_rss_gb"] > 0
    # barrier-aligned anchors: offsets are small and the lead's is 0
    offs = [p["clock_offset_s"] for p in m["processes"]]
    assert min(offs) == 0.0 and max(offs) < 30.0, offs

    # one Perfetto-loadable trace with BOTH processes' pids
    with open(os.path.join(tmp, "merged_trace.json")) as f:
        events = json.load(f)["traceEvents"]
    assert {e["pid"] for e in events} == {1, 2}
    assert any(e["ph"] == "X" and e["name"] == "sync-execute"
               and e["pid"] == 2 for e in events)
    assert any(e["ph"] == "C" for e in events)   # memory counter tracks
