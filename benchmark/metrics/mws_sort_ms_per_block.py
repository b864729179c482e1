"""Device time of the mutex-watershed edge program per block processed in
the traced window: the operations under the ``jax.named_scope``s
``mws_edges`` (every grid edge's priority and packed partner) and
``mws_sort`` (the stable descending sort) of
``ops/mws._sorted_edges_device``, found by the ``op_name`` of each
operation (``benchmark/stage_reduce.py``: ``program_ops``, ``op_names``,
``self_times``; its ``scope_s`` does not list these scopes)."""

import functools
import os

import stage_reduce
import trace_reduce

SCOPES = ("mws_edges", "mws_sort")


@functools.lru_cache(maxsize=2)
def scoped_seconds(path: str, n_devices: int, mtime_ns: int) -> float:
    """Device seconds under :data:`SCOPES` in the trace file ``path``,
    each operation's own time, averaged over the chips."""
    ops = stage_reduce.program_ops(trace_reduce.load(path), n_devices)
    names = stage_reduce.op_names(path)
    ns = 0
    for dev, evs in ops.items():
        plane = names.get(f"/device:TPU:{dev}", {})
        evs = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
        for (name, _, _, pid), t in zip(evs, stage_reduce.self_times(evs)):
            if set(plane.get((pid, name), "").split("/")) & set(SCOPES):
                ns += t
    return ns / max(len(ops), 1) * 1e-9


def read(run):
    red, chains = run.get("trace"), run.get("chains")
    if not red or not chains or "workdir" not in chains[0]:
        return None
    trace_dir = os.path.join(os.path.dirname(chains[0]["workdir"]), "trace")
    try:
        path = trace_reduce.newest_xplane(trace_dir)
    except FileNotFoundError:
        return None
    seconds = scoped_seconds(path, max(red.get("n_devices", 1), 1),
                             os.stat(path).st_mtime_ns)
    blocks = run["blocks_per_chain"] * len(chains)
    if not seconds or not blocks:
        return None
    return 1000.0 * seconds / blocks
