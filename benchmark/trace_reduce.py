"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.  Reads nothing of the program: only the trace the profiler wrote.

* device planes are ``/device:TPU:<n>``; their operations are the events
  of the ``XLA Ops`` line;
* busy time is the union of those events' intervals, per chip, averaged
  over the chips used;
* per-operation time sums the durations of the events of one name, named
  ``<program>/<instruction> <opcode>`` (the program is the ``XLA Modules``
  event the operation starts in);
* custom-call time sums the events of the Pallas kernels (HLO
  ``custom-call`` ops);
* idle gaps are the holes in the first chip's busy time between the start
  of the first and the end of the last ``bench.chain*`` host span
  (``TraceAnnotation``), cut where a ``bench.task.*`` span starts or ends,
  each piece labelled by the innermost ``bench.*`` span open at its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def is_custom_call(name: str) -> bool:
    """The TPU trace names an op by its HLO text: a Pallas kernel is a
    ``custom-call`` with ``custom_call_target="tpu_custom_call"``."""
    return " custom-call(" in name


def short_name(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...), ...`` -> ``fusion.3 fusion``: the
    HLO instruction's name and its opcode."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:64]
    m = re.search(r"\}?\s([a-z][a-z0-9-]*)\(", rhs)
    return f"{lhs.lstrip('%')} {m.group(1)}" if m else lhs.lstrip("%")


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def device_ops(pd, n_devices: int):
    """{device id: [(name, start_ns, end_ns, program)]} for the first
    ``n_devices`` TPU planes."""
    planes = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
    planes.sort(key=lambda p: p[0])
    out = {}
    for dev, plane in planes[:n_devices]:
        evs, mods = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name.split("(")[0]) for ev in line.events)
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
        starts = [m[0] for m in mods]
        named = []
        for name, s, e in evs:
            k = bisect.bisect_right(starts, s) - 1
            prog = mods[k][2] if k >= 0 and s <= mods[k][1] else "?"
            named.append((name, s, e, prog))
        out[dev] = named
    return out


def host_spans(pd, prefix: str = "bench."):
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def idle_gaps(merged, spans):
    """Idle pieces (start, end) of one chip, longest first: the holes in
    ``merged`` inside the extent of the chain spans, cut at task span
    boundaries."""
    chains = [(s, e) for name, s, e in spans if name.startswith("bench.chain")]
    if chains:
        lo, hi = min(s for s, _ in chains), max(e for _, e in chains)
    elif merged:
        lo, hi = merged[0][0], merged[-1][1]
    else:
        return []
    holes, t = [], lo
    for s, e in merged:
        if s > t:
            holes.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        holes.append((t, hi))
    cuts = sorted({x for name, s, e in spans if name.startswith("bench.task.")
                   for x in (s, e)})
    pieces = []
    for s, e in holes:
        inner = [c for c in cuts if s < c < e]
        for a, b in zip([s] + inner, inner + [e]):
            if b > a:
                pieces.append((a, b))
    pieces.sort(key=lambda g: g[0] - g[1])
    return pieces


def reduce(pd, n_devices: int = 1, top: int = 10) -> dict:
    ops = device_ops(pd, n_devices)
    n_dev = max(len(ops), 1)
    busy = 0.0
    per_op = {}
    custom = 0.0
    n_events = 0
    merged0 = []
    for i, (dev, evs) in enumerate(sorted(ops.items())):
        merged = union((s, e) for _, s, e, _ in evs)
        busy += sum(e - s for s, e in merged)
        if i == 0:
            merged0 = merged
        for name, s, e, prog in evs:
            n_events += 1
            key = f"{prog}/{short_name(name)}"
            per_op[key] = per_op.get(key, 0.0) + (e - s)
            if is_custom_call(name):
                custom += e - s
    spans = host_spans(pd)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "n_devices": len(ops),
        "n_events": n_events,
        "busy_s": busy / n_dev * 1e-9,
        "custom_call_s": custom / n_dev * 1e-9,
        "top_ops": [[name, ns / n_dev * 1e-9] for name, ns in top_ops],
        "gaps_ns": idle_gaps(merged0, spans)[:200],
        "spans": spans,
    }


def reduce_dir(trace_dir: str, n_devices: int = 1) -> dict:
    return reduce(load(newest_xplane(trace_dir)), n_devices)


def label_gaps(red: dict, top: int = 10):
    """The longest idle gaps on the first chip as [[label, seconds]], the
    label being the innermost ``bench.*`` span open at the gap's middle,
    or ``unlabelled``."""
    out = []
    for s, e in red["gaps_ns"][:top]:
        mid = 0.5 * (s + e)
        inside = [(se - ss, name) for name, ss, se in red["spans"]
                  if ss <= mid <= se]
        label = min(inside)[1] if inside else "unlabelled"
        out.append([label, (e - s) * 1e-9])
    return out
