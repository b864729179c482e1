"""The stage reduction (``stage_reduce.py``) and the per-layer readers of
its keys, checked against small traces recorded on the chip (one TPU v5
lite each):

* ``data/edt_trace.xplane.pb``: a program with neither stage scopes nor
  program spans (``test_trace_reduce.py``);
* ``data/stage_trace.xplane.pb`` (``data/record_stage_trace.py``):
  a program with stage scopes between program stages on two threads,
  each stage's host work a sleep (the script's docstring lists them).
"""

import os
import shutil

import pytest

import run
import stage_reduce
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "edt_trace.xplane.pb")
STAGE_FIXTURE = os.path.join(DATA, "stage_trace.xplane.pb")


def test_program_without_scopes_or_spans():
    """The existing fixture reads as a program without scopes or stage
    spans: all of its busy time unscoped, no idle split."""
    pd = trace_reduce.load(FIXTURE)
    busy = trace_reduce.reduce(pd, n_devices=1)["busy_s"]
    red = stage_reduce.reduce(pd, FIXTURE, 1)
    assert red["scope_s"] == {"unscoped": pytest.approx(busy, rel=1e-9)}
    assert red["idle_by_stage_s"] is None


def test_op_names_read_the_tf_op_stat():
    """Every device op of the existing fixture finds its ``op_name`` in
    the trace's event metadata (the Pallas EDT is a ``pallas_call`` inside
    the jitted ``_edt_impl``)."""
    names = stage_reduce.op_names(FIXTURE)
    assert set(names) == {"/device:TPU:0"}
    pd = trace_reduce.load(FIXTURE)
    ops = stage_reduce.program_ops(pd, 1)[0]
    by_key = names["/device:TPU:0"]
    found = [by_key[(pid, name)] for name, _, _, pid in ops]
    assert len(found) == trace_reduce.reduce(pd, 1)["n_events"]
    edt = [n for (_, op), n in by_key.items() if op.startswith("%_edt_impl")]
    assert edt and all(n == "jit(<lambda>)/jit(_edt_impl)/pallas_call:"
                       for n in edt)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(run)/edt/jit(_edt_impl)/pallas_call:", "edt"),
    ("jit(run)/watershed/while/body/seeds/add:", "watershed"),
    ("jit(run)/pairs/scatter-add:", "pairs"),
    ("jit(run)/dynamic_slice:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_takes_the_outermost_stage_scope(op_name, scope):
    assert stage_reduce.scope_of(op_name) == scope


def test_self_times_count_nested_ops_once():
    """A loop op holding its body's ops: each nanosecond counts once, so
    the ops sum to the busy time."""
    evs = [("loop", 0, 100, "p"), ("a", 10, 30, "p"), ("b", 40, 50, "p"),
           ("c", 45, 48, "p"), ("d", 120, 130, "p")]
    assert stage_reduce.self_times(evs) == [70, 20, 7, 3, 10]


def test_idle_by_stage_prefers_the_task_threads_stage():
    """Holes of [0, 100] outside the busy interval [40, 50], cut at every
    program span boundary: the task thread's stage wins over a helper's,
    a helper's stage counts where the task thread has none, an attempt
    span alone is ``other``, no program span ``none``."""
    merged = [[40, 50]]
    spans = [("bench.chain0", 0, 100)]
    prog = [
        ("ctt.attempt.t", 0, 80, "main"),
        ("ctt.stage.host-map", 0, 20, "main"),
        ("ctt.stage.store-write", 10, 30, "pool"),
        ("ctt.stage.h2d-upload", 55, 60, "main"),
        ("ctt.stage.prefetch-wait", 60, 65, "main"),
        ("ctt.pool.pool:write", 60, 90, "pool")]
    classes, by_span = stage_reduce.idle_by_stage(merged, spans, prog,
                                                  by_span=True)
    ns = 1e-9
    assert classes["host"] == pytest.approx(20 * ns)     # [0, 20): main's
    # [20, 30): the helper's write, main has no stage; [60, 65): the wait
    assert classes["store"] == pytest.approx(15 * ns)
    assert classes["transfer"] == pytest.approx(5 * ns)  # [55, 60)
    # [30, 40), [50, 55), [65, 80) under the attempt; [80, 90) under the
    # helper's pool span: other.  [90, 100) nothing: none
    assert classes["other"] == pytest.approx(40 * ns)
    assert classes["none"] == pytest.approx(10 * ns)
    assert sum(classes.values()) == pytest.approx(90 * ns)
    assert by_span["ctt.attempt.t"] == pytest.approx(30 * ns)
    assert by_span["ctt.pool.pool:write"] == pytest.approx(10 * ns)


def test_idle_by_stage_is_none_without_program_stages():
    merged = [[40, 50]]
    spans = [("bench.chain0", 0, 100)]
    assert stage_reduce.idle_by_stage(merged, spans, []) is None
    assert stage_reduce.idle_by_stage(
        merged, spans, [("ctt.attempt.t", 0, 80, "main")]) is None


@pytest.mark.parametrize("name, cls", [
    ("ctt.stage.store-read", "store"), ("ctt.stage.prefetch-wait", "store"),
    ("ctt.stage.host-solve", "host"), ("ctt.stage.fetch-rle", "transfer"),
    ("ctt.stage.d2h-labels", "transfer"), ("ctt.stage.sync-execute", "other"),
    ("ctt.job.watershed:job0", "other"), (None, "none"),
])
def test_idle_class_of_a_span(name, cls):
    assert stage_reduce.idle_class(name) == cls


#: the stage fixture's reduction.  On the TPU the matmul under ``edt``
#: compiles to a fusion whose metadata carries no ``op_name``, so only the
#: sort's ``watershed`` scope shows; the cumsum and sum are unscoped
STAGE_EXPECTED = {
    "busy_s": 0.008982952,
    "scope_s": {"unscoped": 0.000700387, "watershed": 0.008282565},
    "idle_by_stage_s": {"store": 0.028259489, "host": 0.031485126,
                        "transfer": 0.011251798, "other": 0.030009387,
                        "none": 0.021289296},
}


@pytest.fixture(scope="module")
def stage_red():
    pd = trace_reduce.load(STAGE_FIXTURE)
    return (pd, trace_reduce.reduce(pd, n_devices=1),
            stage_reduce.reduce(pd, STAGE_FIXTURE, 1))


def test_stage_fixture_scopes(stage_red):
    """Device seconds per scope, to the nanosecond, summing to the busy
    time."""
    _, red, stages = stage_red
    assert red["busy_s"] == pytest.approx(STAGE_EXPECTED["busy_s"], rel=1e-9)
    assert stages["scope_s"] == pytest.approx(STAGE_EXPECTED["scope_s"],
                                              rel=1e-9)
    assert sum(stages["scope_s"].values()) == pytest.approx(red["busy_s"],
                                                            rel=1e-9)


def test_stage_fixture_idle_by_stage(stage_red):
    """The window's idle time by class, to the nanosecond; the classes sum
    to the idle time; each class holds the host work the recording put
    there (sleeps of 30 ms host-map, 10 + 20 ms store, 20 ms with the
    attempt span alone, 20 ms with no program span)."""
    pd, red, stages = stage_red
    idle = stages["idle_by_stage_s"]
    assert idle == pytest.approx(STAGE_EXPECTED["idle_by_stage_s"], rel=1e-9)
    merged = stage_reduce.busy_intervals(pd, 1)
    lo, hi = stage_reduce.window(merged, red["spans"])
    total = sum(e - s for s, e in stage_reduce.holes(merged, lo, hi)) * 1e-9
    assert sum(idle.values()) == pytest.approx(total, rel=1e-9)
    # the window's holes are the idle gaps the harness already cuts
    assert total == pytest.approx(
        sum(e - s for s, e in red["gaps_ns"]) * 1e-9, rel=1e-9)
    assert idle["host"] == pytest.approx(0.03, abs=0.005)
    assert idle["store"] == pytest.approx(0.03, abs=0.005)
    assert idle["other"] >= 0.02 and idle["none"] >= 0.02
    assert idle["transfer"] > 0


def test_stage_fixture_spans_keep_their_threads(stage_red):
    """The helper thread's ``store-write`` sits on its own host line, the
    task thread's stages on the line of its attempt span."""
    pd, _, _ = stage_red
    lines = {}
    for name, _, _, thread in stage_reduce.program_spans(pd):
        lines.setdefault(name, set()).add(thread)
    task = lines.pop("ctt.attempt.fixture")
    helper = lines.pop("ctt.stage.store-write")
    assert len(task) == 1 and len(helper) == 1 and task != helper
    assert all(t == task for t in lines.values()), lines


# -- the per-layer readers -------------------------------------------------------


READ = {
    "resident_ms_per_block.watershed": 50.0,
    "resident_ms_per_block.pairs_hist": 150.0,
    "resident_ms_per_block.relabel": 25.0,
    "idle_s.store": 2.0, "idle_s.store.fragments": 2.0,
    "idle_s.host": 1.0, "idle_s.host.fragments": 1.0,
}


def _read(name, run_):
    reader = run.load_module(os.path.join(run.HERE, "metrics", name + ".py"),
                             name)
    return reader.read(run_)


@pytest.mark.parametrize("name", sorted(READ))
def test_scope_and_idle_readers(name, monkeypatch):
    """The readers of the program's scopes and stage spans: device seconds
    per block of two 27-block chains, idle seconds per chain; silent (no
    value, no error) on the reduction of a program that has neither."""
    reduced = {"scope_s": {"watershed": 2.7, "pairs": 5.4, "edge_stats": 2.7,
                           "relabel": 1.35, "unscoped": 0.1},
               "idle_by_stage_s": {"store": 4.0, "host": 2.0,
                                   "transfer": 0.5, "other": 1.0,
                                   "none": 0.5}}
    run_ = {"trace": {"n_devices": 1}, "chains": [{}, {}],
            "blocks_per_chain": 27, "window_s": 80.0}
    monkeypatch.setattr(stage_reduce, "of_run", lambda _: reduced)
    assert _read(name, run_) == pytest.approx(READ[name])
    reduced = {"scope_s": {"unscoped": 3.0}, "idle_by_stage_s": None}
    assert _read(name, run_) is None


def _harness_layout(tmp_path, fixture):
    """A harness run's layout: the trace under ``<work>/trace`` as the
    profiler writes it, beside the chains' workdirs."""
    work = tmp_path / "cell"
    prof = work / "trace" / "plugins" / "profile" / "2026_01_01_00_00_00"
    prof.mkdir(parents=True)
    shutil.copy(fixture, prof / "host.xplane.pb")
    return {"trace": trace_reduce.reduce_dir(str(work / "trace")),
            "chains": [{"workdir": str(work / "chain0")}],
            "blocks_per_chain": 2, "window_s": 1.0}


@pytest.mark.parametrize("name, expected", [
    ("resident_ms_per_block.watershed",
     1000.0 * STAGE_EXPECTED["scope_s"]["watershed"] / 2),
    ("resident_ms_per_block.pairs_hist", None),
    ("resident_ms_per_block.relabel", None),
    ("idle_s.store", STAGE_EXPECTED["idle_by_stage_s"]["store"]),
    ("idle_s.host.fragments", STAGE_EXPECTED["idle_by_stage_s"]["host"]),
])
def test_readers_find_the_harness_trace(tmp_path, name, expected):
    """On a run laid out as the harness lays it out, the readers reduce
    the trace it wrote: the stage fixture's numbers, none for a scope it
    lacks; on a program without scopes or spans, and without a trace, no
    value and no error."""
    run_ = _harness_layout(tmp_path / "stage", STAGE_FIXTURE)
    got = _read(name, run_)
    assert got == (None if expected is None
                   else pytest.approx(expected, rel=1e-9))
    assert _read(name, _harness_layout(tmp_path / "edt", FIXTURE)) is None
    shutil.rmtree(tmp_path / "stage" / "cell" / "trace")
    assert _read(name, run_) is None
