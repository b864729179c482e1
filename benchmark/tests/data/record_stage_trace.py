#!/usr/bin/env python3
"""Record ``stage_trace.xplane.pb``, the fixture of ``test_stage_reduce.py``
for the scope and idle-by-stage reductions.  Runs on the chip only:

    python3 benchmark/tests/data/record_stage_trace.py <out.xplane.pb>

Inside one ``bench.chain0`` span (the window) the main thread holds the
attempt span ``ctt.attempt.fixture`` and runs a program with two stage
scopes (``edt``: a matmul, ``watershed``: a sort) and an unscoped cumsum
and sum, between host work that leaves the chip idle:

1. ``host-map`` on the main thread (30 ms sleep) while a helper thread
   sleeps 40 ms in ``store-write``: host, then, once the main thread has
   left its stage and waits for the helper, store (about 10 ms);
2. an ``h2d-upload`` of 32 MiB: transfer;
3. ``prefetch-wait`` on the main thread (20 ms): store;
4. a 20 ms sleep with the attempt span open and no stage: other;
5. after the attempt span, a 20 ms sleep inside the window: none.

Prints the reduction of the recorded trace.  On the TPU the matmul's
fusion carries no ``op_name``, so the recorded trace shows the
``watershed`` scope alone (``test_stage_reduce.py``, ``STAGE_EXPECTED``).
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

import stage_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from cluster_tools_tpu.core import runtime, telemetry  # noqa: E402


@jax.jit
def scoped(x):
    with jax.named_scope("edt"):
        y = jnp.dot(x, x)
    with jax.named_scope("watershed"):
        y = jnp.sort(y, axis=1)
    return jnp.cumsum(y, axis=0).sum()


def helper_write(seconds):
    with runtime.stage("store-write"):
        time.sleep(seconds)


def record(trace_dir):
    x = jnp.ones((2048, 2048), jnp.float32)
    scoped(x).block_until_ready()           # compiled outside the trace
    host = np.ones((2048, 4096), np.float32)
    jax.profiler.start_trace(trace_dir)
    with TraceAnnotation("bench.chain0"):
        with telemetry.span("fixture", cat="attempt"):
            scoped(x).block_until_ready()
            helper = threading.Thread(target=helper_write, args=(0.04,))
            with runtime.stage("host-map"):
                helper.start()
                time.sleep(0.03)
            helper.join()
            scoped(x).block_until_ready()
            with runtime.stage("h2d-upload"):
                jax.device_put(host).block_until_ready()
            scoped(x).block_until_ready()
            with runtime.stage("prefetch-wait"):
                time.sleep(0.02)
            scoped(x).block_until_ready()
            time.sleep(0.02)
            scoped(x).block_until_ready()
        time.sleep(0.02)
        scoped(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace_reduce.newest_xplane(trace_dir)


def main(out):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("records a TPU trace: run it on the chip")
    tmp = tempfile.mkdtemp()
    try:
        path = record(tmp)
        shutil.copy(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    pd = trace_reduce.load(out)
    red = trace_reduce.reduce(pd, 1)
    stages = stage_reduce.reduce(pd, out, 1)
    _, by_span = stage_reduce.idle_by_stage(
        stage_reduce.busy_intervals(pd, 1), red["spans"],
        stage_reduce.program_spans(pd), by_span=True)
    names = stage_reduce.op_names(out)
    print(json.dumps({
        "bytes": os.path.getsize(out), "n_events": red["n_events"],
        "busy_s": red["busy_s"], "scope_s": stages["scope_s"],
        "idle_by_stage_s": stages["idle_by_stage_s"], "by_span": by_span,
        "op_names": sorted({v for ops in names.values()
                            for v in ops.values()}),
        "program_spans": sorted({n for n, *_ in
                                 stage_reduce.program_spans(pd)}),
    }, indent=1))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1
         else os.path.join(HERE, "stage_trace.xplane.pb"))
