"""Reduce a JAX profiler trace (``.xplane.pb``) to the program's own stage
numbers, beside :mod:`trace_reduce` (whose keys and values this leaves as
they are).  Reads nothing of the program: only the trace the profiler
wrote.

* scope time (``scope_s``) sums the device time of the operations under
  each stage scope of the program's device programs (``jax.named_scope``:
  :data:`SCOPES`), read from the ``tf_op`` stat of each operation's event
  metadata, which holds its HLO ``op_name`` (a fusion carries its root's);
  operations under none are summed as ``unscoped``;
* idle time by host stage (``idle_by_stage_s``) splits every hole of the
  first chip's busy time in the window of :mod:`trace_reduce` (the
  ``bench.chain*`` spans) by the program's own spans (``ctt.stage.<stage>``,
  ``ctt.<cat>.<name>``: the program opens them in any profiler trace) into
  the classes of :data:`IDLE_CLASSES`.

The per-layer readers reach the trace through :func:`of_run`, which finds
it where ``run.py`` writes it (``<work>/trace``, beside the chains'
``<work>/chain<i>``) before the harness deletes it, and reduces each file
once per process.
"""

from __future__ import annotations

import bisect
import functools
import os

import trace_reduce
from trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE

#: the event-metadata stat holding an operation's HLO ``op_name``
OP_NAME_STAT = "tf_op"
#: the stage scopes of the resident program
#: (``workflows/fused_pipeline._resident_program``) and of the watershed
#: pipeline that shares its core (``workflows/watershed._ws_pipeline_3d``)
SCOPES = ("edt", "smooth", "seeds", "watershed", "relabel", "pairs",
          "edge_stats", "rle")
#: the program's own host spans in a profiler trace
PROGRAM_PREFIX = "ctt."
STAGE_PREFIX = "ctt.stage."
ATTEMPT_PREFIX = "ctt.attempt."
#: idle classes by the stage a piece of idle time falls in; ``other`` is
#: any other program span (another stage, or an attempt, job or pool span
#: with no stage open), ``none`` no program span at all
IDLE_CLASSES = ("store", "host", "transfer", "other", "none")
_STAGE_CLASSES = (("store", ("store-", "prefetch-wait")),
                  ("host", ("host-",)),
                  ("transfer", ("h2d-", "d2h-", "fetch-")))


def window(merged, spans):
    """(start, end) of the measured window: from the start of the first to
    the end of the last chain span, else the extent of the busy time;
    None with neither (as :func:`trace_reduce.idle_gaps` takes it)."""
    chains = [(s, e) for name, s, e in spans if name.startswith("bench.chain")]
    if chains:
        return min(s for s, _ in chains), max(e for _, e in chains)
    if merged:
        return merged[0][0], merged[-1][1]
    return None


def holes(merged, lo, hi):
    """The holes (start, end) in the merged busy intervals inside
    [lo, hi]."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return out


# -- operation scopes ---------------------------------------------------------


def program_ops(pd, n_devices: int):
    """{device id: [(name, start_ns, end_ns, program id)]} for the first
    ``n_devices`` TPU planes: :func:`trace_reduce.device_ops` with the id
    of the program each operation starts in (``jit_run(<id>)`` on the
    ``XLA Modules`` line, the ``program_id`` of its operations)."""
    planes = sorted((int(m.group(1)), plane) for plane in pd.planes
                    if (m := DEVICE_PLANE.match(plane.name)))
    out = {}
    for dev, plane in planes[:n_devices]:
        evs, mods = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name.partition("(")[2].rstrip(")"))
                              for ev in line.events)
            elif line.name == OPS_LINE:
                evs.extend((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events)
        starts = [m[0] for m in mods]
        named = []
        for name, s, e in evs:
            k = bisect.bisect_right(starts, s) - 1
            prog = mods[k][2] if k >= 0 and s <= mods[k][1] else ""
            named.append((name, s, e, prog))
        out[dev] = named
    return out


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of the protobuf message in ``buf[start:end]``:
    an int for a varint field, a (start, end) span for a length-delimited
    one; fixed-width fields are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def op_names(path: str) -> dict:
    """{device plane name: {(program id, operation name): op_name}} from the
    event metadata of the trace's TPU planes: the ``tf_op`` stat, which the
    profiler fills with the operation's HLO ``op_name`` (the jit's name,
    then each ``jax.named_scope`` it ran under).  ``ProfileData`` exposes
    no event metadata, so this reads the file's protobuf (``XSpace``:
    planes 1; ``XPlane``: name 2, event_metadata 4, stat_metadata 5;
    ``XEventMetadata``: name 2, stats 5; ``XStat``: metadata_id 1, uint64
    3, int64 4, str 5, ref 7; ``XStatMetadata``: id 1, name 2)."""
    with open(path, "rb") as f:
        buf = f.read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = text(pv)
            elif pf == 4:
                metas.append(pv)
            elif pf == 5:
                for ef, ev in _fields(buf, *pv):
                    if ef == 2:
                        md = dict(_fields(buf, *ev))
                        stat_names[md.get(1, 0)] = text(md[2]) if 2 in md \
                            else ""
        if not DEVICE_PLANE.match(name):
            continue
        by_name = {v: k for k, v in stat_names.items()}
        op_id, prog_id = by_name.get(OP_NAME_STAT), by_name.get("program_id")
        ops = out.setdefault(name, {})
        for entry in metas:
            for ef, ev in _fields(buf, *entry):
                if ef != 2:
                    continue
                op, prog, op_name = "", "", None
                for mf, mv in _fields(buf, *ev):
                    if mf == 2:
                        op = text(mv)
                    elif mf == 5:
                        st = dict(_fields(buf, *mv))
                        if st.get(1) == op_id:
                            op_name = (text(st[5]) if 5 in st
                                       else stat_names.get(st.get(7), ""))
                        elif st.get(1) == prog_id:
                            prog = str(st.get(3, st.get(4, "")))
                if op_name is not None:
                    ops[(prog, op)] = op_name
    return out


def scope_of(op_name: str) -> str:
    """The first component of an ``op_name`` path that is one of
    :data:`SCOPES`, else ``unscoped``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return "unscoped"


def self_times(evs):
    """Each event's duration less the part of it that the events nested in
    it cover (an op can hold others on the same line), so that the events
    of one chip sum to its busy time; ``evs`` sorted by (start, -end)."""
    out = [e - s for _, s, e, _ in evs]
    stack = []
    for i, (_, s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return out


def scope_seconds(ops_by_id: dict, names: dict) -> dict:
    """Device seconds per scope of :data:`SCOPES` and ``unscoped``, summed
    over the operations' own times and averaged over the chips, from
    :func:`program_ops` and :func:`op_names`."""
    ns = {}
    for dev, evs in ops_by_id.items():
        plane = names.get(f"/device:TPU:{dev}", {})
        evs = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
        for (name, _, _, pid), t in zip(evs, self_times(evs)):
            scope = scope_of(plane.get((pid, name), ""))
            ns[scope] = ns.get(scope, 0) + t
    ns.setdefault("unscoped", 0)
    n_dev = max(len(ops_by_id), 1)
    return {k: v / n_dev * 1e-9 for k, v in sorted(ns.items())}


# -- idle time by host stage --------------------------------------------------


def program_spans(pd):
    """The program's host spans (:data:`PROGRAM_PREFIX`) as (name,
    start_ns, end_ns, thread), the thread being the (plane, line) of the
    host trace that recorded it: one line per OS thread."""
    out = []
    for p, plane in enumerate(pd.planes):
        if DEVICE_PLANE.match(plane.name):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, (p, k)))
    return out


def idle_class(span_name) -> str:
    """The class of :data:`IDLE_CLASSES` of a piece counted under
    ``span_name`` (None: no program span open)."""
    if span_name is None:
        return "none"
    if not span_name.startswith(STAGE_PREFIX):
        return "other"
    stage = span_name[len(STAGE_PREFIX):]
    for cls, prefixes in _STAGE_CLASSES:
        if stage.startswith(prefixes):
            return cls
    return "other"


def _innermost(spans):
    """The span of ``spans`` (start, end, name, thread) opened last."""
    return max(spans, key=lambda sp: (sp[0], -sp[1]))


def _counted_under(open_spans):
    """The span a piece of idle time is counted under, from the program
    spans open over it: the innermost stage of the thread that runs the
    task (the one holding the innermost attempt span), else the innermost
    stage of any other thread; with no stage open, the task thread's
    innermost span, else any thread's."""
    if not open_spans:
        return None
    attempts = [sp for sp in open_spans if sp[2].startswith(ATTEMPT_PREFIX)]
    task = _innermost(attempts)[3] if attempts else None
    stages = [sp for sp in open_spans if sp[2].startswith(STAGE_PREFIX)]
    for pool in ([sp for sp in stages if sp[3] == task], stages,
                 [sp for sp in open_spans if sp[3] == task], open_spans):
        if pool:
            return _innermost(pool)[2]


def segments(prog):
    """Sorted boundary times of the program spans ``prog``
    (:func:`program_spans`) and, for each stretch between two consecutive
    ones, the name of the span its idle time is counted under (None: no
    program span open)."""
    cuts = sorted({t for _, s, e, _ in prog for t in (s, e)})
    starts = sorted((s, e, name, th) for name, s, e, th in prog if e > s)
    under, active, j = [], [], 0
    for t in cuts[:-1]:
        active = [sp for sp in active if sp[1] > t]
        while j < len(starts) and starts[j][0] <= t:
            if starts[j][1] > t:
                active.append(starts[j])
            j += 1
        under.append(_counted_under(active))
    return cuts, under


def split_holes(merged, lo, hi, cuts, under):
    """Yield (start, end, span name or None) for every piece of the holes
    of ``merged`` in [lo, hi], cut at ``cuts`` (:func:`segments`)."""
    for s, e in holes(merged, lo, hi):
        k = bisect.bisect_right(cuts, s) - 1
        while s < e:
            b = min(cuts[k + 1], e) if k + 1 < len(cuts) else e
            yield s, b, (under[k] if 0 <= k < len(under) else None)
            s, k = b, k + 1


def idle_by_stage(merged, spans, prog, by_span: bool = False):
    """Idle seconds of one chip inside the window (:func:`window`) by the
    class of :data:`IDLE_CLASSES` of the program span each piece is
    counted under: every hole in ``merged`` is cut at every boundary of a
    program span in ``prog`` (:func:`program_spans`), and each piece goes
    to the span :func:`_counted_under` picks from the spans open over it
    (:func:`segments`, :func:`split_holes`).
    The classes sum to the window's idle time.  With ``by_span``, also the
    seconds per span name.  None where the trace holds no program stage
    span (a program that does not open them) or no window."""
    w = window(merged, spans)
    if w is None or not any(sp[0].startswith(STAGE_PREFIX) for sp in prog):
        return (None, None) if by_span else None
    classes = dict.fromkeys(IDLE_CLASSES, 0)
    names = {}
    for s, e, name in split_holes(merged, *w, *segments(prog)):
        classes[idle_class(name)] += e - s
        names[name or "none"] = names.get(name or "none", 0) + e - s
    classes = {c: ns * 1e-9 for c, ns in classes.items()}
    if by_span:
        return classes, {n: ns * 1e-9 for n, ns in sorted(
            names.items(), key=lambda kv: -kv[1])}
    return classes


# -- one trace ----------------------------------------------------------------


def busy_intervals(pd, n_devices: int):
    """The merged busy intervals of the first chip of
    :func:`trace_reduce.device_ops` (the chip whose holes
    :func:`trace_reduce.idle_gaps` cuts)."""
    ops = trace_reduce.device_ops(pd, n_devices)
    if not ops:
        return []
    return trace_reduce.union((s, e) for _, s, e, _ in ops[min(ops)])


def reduce(pd, path: str, n_devices: int = 1) -> dict:
    """``scope_s`` and ``idle_by_stage_s`` of the trace ``pd`` read from
    the file ``path``.  ``scope_s`` is None where no chip ran an
    operation."""
    ops = program_ops(pd, n_devices)
    n_events = sum(len(evs) for evs in ops.values())
    return {
        "scope_s": (scope_seconds(ops, op_names(path)) if n_events
                    else None),
        "idle_by_stage_s": idle_by_stage(
            busy_intervals(pd, n_devices), trace_reduce.host_spans(pd),
            program_spans(pd)),
    }


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, n_devices: int, mtime_ns: int) -> dict:
    """:func:`reduce` of one ``.xplane.pb``, once per file and version."""
    return reduce(trace_reduce.load(path), path, n_devices)


def of_run(run) -> dict:
    """The stage reduction of the trace of a harness run (the ``run`` its
    per-layer readers get), found beside the run's chain workdirs;
    ``scope_s`` and ``idle_by_stage_s`` None where there is no trace."""
    empty = {"scope_s": None, "idle_by_stage_s": None}
    red, chains = run.get("trace"), run.get("chains")
    if not red or not chains or "workdir" not in chains[0]:
        return empty
    trace_dir = os.path.join(os.path.dirname(chains[0]["workdir"]), "trace")
    try:
        path = trace_reduce.newest_xplane(trace_dir)
    except FileNotFoundError:
        return empty
    return reduce_file(path, max(red.get("n_devices", 1), 1),
                       os.stat(path).st_mtime_ns)
