"""Structured span tracing (core/telemetry.py) — ISSUE 15.

Tier-1 coverage for the span recorder (thread safety, ring bound,
parent/child nesting, off-by-default zero-recording), the profiler
sink (program spans in a JAX profiler trace), the Chrome trace-event
exporter (schema, nesting, fixed-clock determinism), the span-derived
rollups (stage seconds, queue-wait histograms), the Prometheus writer, the stage-name registry lint, the
runtime instrumentation (stage_add span emission with bit-identical
accumulators, BoundedPool queue-wait spans, attempt spans + correlation
ids across retries), and the telemetry-off overhead gate.  No XLA
compiles anywhere (PR 13 conftest pattern).
"""

import json
import os
import re
import threading
import time

import pytest

from cluster_tools_tpu.core import runtime, telemetry
from cluster_tools_tpu.core.config import ConfigDir

from test_runtime import FailingTask, FillTask


class FakeClock:
    """Deterministic fixed-step clock for byte-identical trace exports."""

    def __init__(self, step=0.001):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


@pytest.fixture()
def fake_clock():
    clk = FakeClock()
    telemetry.configure(enabled=True, clock=clk)
    return clk


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------

def test_disabled_records_nothing():
    """Off by default: spans, stage hooks and context managers are all
    no-ops, and the disabled span context is a shared singleton (the
    off-path allocates nothing)."""
    assert not telemetry.enabled()
    telemetry.record("x", 0.0, 1.0)
    telemetry.record_stage("sync-execute", 1.0)
    ctx = telemetry.span("x")
    with ctx:
        runtime.stage_add("host-map", 1.0)
    assert ctx is telemetry.span("y")        # shared null span
    assert telemetry.spans_snapshot() == []


def test_span_nesting_and_parents(fake_clock):
    """task -> job -> block -> stage: children link to the innermost
    enclosing span on the same thread, both for `span` contexts and for
    post-hoc `record`/`record_stage` calls."""
    with telemetry.span("t", cat="task") as t:
        with telemetry.span("j", cat="job") as j:
            with telemetry.span("b", cat="block") as b:
                telemetry.record_stage("sync-execute", 0.5)
            telemetry.record("d2h-dense", 1.0, 2.0)
    spans = {s.name: s for s in telemetry.spans_snapshot()}
    assert spans["t"].parent is None
    assert spans["j"].parent == t.sid
    assert spans["b"].parent == j.sid
    assert spans["sync-execute"].parent == b.sid
    assert spans["d2h-dense"].parent == j.sid     # block already closed
    # durations are monotone and nested
    assert spans["t"].t0 < spans["j"].t0 < spans["b"].t0
    assert spans["b"].t1 < spans["j"].t1 < spans["t"].t1


def test_ring_bound_and_dropped_count(fake_clock):
    telemetry.configure(ring_size=8)
    for i in range(20):
        telemetry.record("host-map", float(i), float(i) + 0.5)
    spans = telemetry.spans_snapshot()
    assert len(spans) == 8
    # newest survive, oldest dropped
    assert [s.t0 for s in spans] == [float(i) for i in range(12, 20)]
    assert telemetry.dropped_count() == 12


def test_recorder_thread_safety(fake_clock):
    """8 threads recording concurrently: no lost spans, unique sids."""
    n_threads, n_iter = 8, 200
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        for _ in range(n_iter):
            with telemetry.span("host-map", cat="stage"):
                pass

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = telemetry.spans_snapshot()
    assert len(spans) == n_threads * n_iter
    assert len({s.sid for s in spans}) == len(spans)


# ---------------------------------------------------------------------------
# Chrome trace exporter
# ---------------------------------------------------------------------------

def _record_fixture_trace():
    with telemetry.span("fill_j0", cat="job", job_id=0):
        with telemetry.span("block:0", cat="block", block=0):
            telemetry.record_stage("sync-execute", 0.002)
        with telemetry.span("block:1", cat="block", block=1):
            telemetry.record_stage("d2h-dense", 0.001)


def test_chrome_trace_schema(fake_clock, tmp_path):
    """Exported JSON is the trace-event object format Perfetto accepts:
    a traceEvents list of complete 'X' events with name/ph/ts/dur/pid/
    tid, plus 'M' process/thread metadata."""
    _record_fixture_trace()
    path = str(tmp_path / "trace.json")
    n = telemetry.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert len(events) == n
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 5 and ms, events
    assert any(e["name"] == "process_name" for e in ms)
    assert any(e["name"] == "thread_name" for e in ms)
    for e in xs:
        for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert key in e, (key, e)
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["tid"], int) and e["tid"] >= 1


def test_chrome_trace_nesting(fake_clock, tmp_path):
    """Block events sit time-nested inside their job event and carry the
    parent sid in args (the hierarchy survives the flat event list)."""
    _record_fixture_trace()
    path = str(tmp_path / "trace.json")
    telemetry.export_chrome_trace(path)
    with open(path) as f:
        xs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    by_name = {e["name"]: e for e in xs}
    job = by_name["fill_j0"]
    for bname in ("block:0", "block:1"):
        blk = by_name[bname]
        assert blk["args"]["parent"] == job["args"]["sid"]
        assert blk["ts"] >= job["ts"]
        assert blk["ts"] + blk["dur"] <= job["ts"] + job["dur"]
    stg = by_name["sync-execute"]
    assert stg["args"]["parent"] == by_name["block:0"]["args"]["sid"]


def test_chrome_trace_deterministic_under_fixed_clock(tmp_path):
    """Identical recordings under an injected fixed clock export
    byte-identical files (dense tid remap, pinned pid, sorted keys)."""
    outs = []
    for i in range(2):
        telemetry.reset()
        telemetry.configure(enabled=True, clock=FakeClock())
        _record_fixture_trace()
        path = str(tmp_path / f"trace_{i}.json")
        telemetry.export_chrome_trace(path)
        with open(path, "rb") as f:
            outs.append(f.read())
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# rollups
# ---------------------------------------------------------------------------

def test_rollups_exact_on_known_intervals(fake_clock):
    """Stage seconds and entries, the trace window and the queue-wait
    histogram against hand-checkable interval arithmetic."""
    telemetry.record("sync-execute", 0.0, 1.0)
    telemetry.record("d2h-dense", 0.5, 1.5)       # overlaps the first
    telemetry.record("host-map", 0.0, 3.0)
    telemetry.record("sync-execute", 2.0, 2.5, count=2)
    telemetry.record("wait-a", 0.0, 0.005, cat="queue-wait")
    telemetry.record("wait-b", 0.0, 0.05, cat="queue-wait")
    spans = telemetry.spans_snapshot()
    assert telemetry.trace_window(spans) == pytest.approx(3.0)
    hist = telemetry.queue_wait_histogram(
        bins=(0.01, 0.1), spans=spans)
    assert hist["count"] == 2
    assert hist["sum"] == pytest.approx(0.055)
    assert hist["buckets"]["0.01"] == 1
    assert hist["buckets"]["0.1"] == 2
    assert hist["buckets"]["+Inf"] == 2
    summ = telemetry.summary(wall=4.0)
    assert summ["stage_seconds"] == {"host-map": 3.0, "sync-execute": 1.5,
                                     "d2h-dense": 1.0}
    assert summ["stage_entries"] == {"sync-execute": 3, "host-map": 1,
                                     "d2h-dense": 1}
    assert summ["window_s"] == pytest.approx(3.0)
    assert summ["wall_s"] == pytest.approx(4.0)
    assert summ["by_cat"]["queue-wait"] == 2


# ---------------------------------------------------------------------------
# runtime instrumentation
# ---------------------------------------------------------------------------

def test_stage_add_emits_spans_and_preserves_counts(fake_clock):
    """Every stage accumulation doubles as a span WITHOUT touching the
    accumulators: deltas are identical to a telemetry-off run of the
    same calls."""
    cn0 = runtime.counts_snapshot()
    st0 = runtime.stages_snapshot()
    runtime.stage_add("sync-execute", 0.5, 3)
    with runtime.stage("host-map"):
        pass
    on_counts = runtime.counts_delta(cn0)
    on_stages = runtime.stages_delta(st0)
    spans = telemetry.spans_snapshot()
    assert [s.name for s in spans] == ["sync-execute", "host-map"]
    assert spans[0].t1 - spans[0].t0 == pytest.approx(0.5)
    assert spans[0].attrs["count"] == 3

    telemetry.configure(enabled=False)
    cn1 = runtime.counts_snapshot()
    runtime.stage_add("sync-execute", 0.5, 3)
    with runtime.stage("host-map"):
        pass
    assert runtime.counts_delta(cn1) == on_counts == \
        {"sync-execute": 3, "host-map": 1}
    assert len(telemetry.spans_snapshot()) == 2   # nothing new recorded
    assert on_stages["sync-execute"] == pytest.approx(0.5)


def test_timed_stage_alias():
    assert runtime.timed_stage is runtime.stage


def test_bounded_pool_spans(fake_clock):
    """Pool submissions record a submit->start queue-wait span and a
    worker-side execution span; inline mode (max_workers=0) records
    nothing extra."""
    done = []
    with runtime.BoundedPool(2) as pool:
        for i in range(4):
            pool.submit(done.append, i)
    spans = telemetry.spans_snapshot()
    waits = [s for s in spans if s.cat == "queue-wait"]
    execs = [s for s in spans if s.cat == "pool"]
    assert sorted(done) == [0, 1, 2, 3]
    assert len(waits) == 4 and len(execs) == 4
    assert all(s.name == "pool-queue-wait" for s in waits)
    assert all(s.name == "pool:append" for s in execs)
    assert telemetry.queue_wait_histogram()["count"] == 4

    n0 = len(telemetry.spans_snapshot())
    with runtime.BoundedPool(0) as pool:          # inline reference mode
        pool.submit(done.append, 99)
    assert len(telemetry.spans_snapshot()) == n0


def test_global_config_arms_telemetry(tmp_path):
    """telemetry_enabled/telemetry_ring_size in the global config arm the
    recorder at task construction (the workflow-level opt-in, mirroring
    exec_cache_dir)."""
    config_dir = str(tmp_path / "configs")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "telemetry_enabled": True,
         "telemetry_ring_size": 128})
    assert not telemetry.enabled()
    FillTask(output_path=str(tmp_path / "o.n5"), output_key="d",
             shape=(10, 10, 10), tmp_folder=str(tmp_path / "tmp"),
             config_dir=config_dir, max_jobs=1, target="inline")
    assert telemetry.enabled()
    telemetry.record("host-map", 0.0, 1.0)
    assert len(telemetry.spans_snapshot()) == 1


def test_attempt_spans_and_correlation_id_across_retries(tmp_path):
    """Block-granular retry: every attempt emits a span carrying the
    SAME correlation id and its attempt number, and the status JSON
    carries the id too (trace <-> status join key)."""
    config_dir = str(tmp_path / "configs")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "max_num_retries": 2,
         "telemetry_enabled": True})
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir)
    out = str(tmp_path / "out.n5")
    task = FailingTask(output_path=out, output_key="data",
                       shape=(20, 20, 20), tmp_folder=str(tmp_path / "t"),
                       config_dir=config_dir, max_jobs=4,
                       target="threads")
    orig = task.run_jobs

    def run_jobs(block_list, cfg, **kw):
        return orig(block_list, {**cfg, "marker_dir": marker_dir}, **kw)

    task.run_jobs = run_jobs
    task.run()
    attempts = [s for s in telemetry.spans_snapshot()
                if s.cat == "attempt"]
    # first run + at least one retry (odd blocks queued BEHIND a failing
    # block only get their marker on the next attempt, so the cascade
    # may take 2 retries); attempt numbers are contiguous from 0
    assert len(attempts) >= 2
    assert sorted(s.attrs["attempt"] for s in attempts) == \
        list(range(len(attempts)))
    corr = {s.attrs["correlation_id"] for s in attempts}
    assert len(corr) == 1 and corr != {""}
    with open(task.output().path) as f:
        status = json.load(f)
    assert status["correlation_id"] == corr.pop()
    assert status["retries"] == len(attempts) - 1
    # job spans run on executor WORKER threads (parenting is per-thread,
    # so they have no parent sid) but are time-nested within an attempt
    jobs = [s for s in telemetry.spans_snapshot() if s.cat == "job"]
    assert jobs
    for j in jobs:
        assert any(a.t0 <= j.t0 and j.t1 <= a.t1 for a in attempts), j


def test_metrics_path_writes_prometheus_snapshot(tmp_path):
    """The metrics_path global-config key makes every status write drop a
    Prometheus snapshot of the runtime counters."""
    mp = str(tmp_path / "task_metrics.prom")
    config_dir = str(tmp_path / "configs")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "metrics_path": mp})
    task = FillTask(output_path=str(tmp_path / "o.n5"), output_key="d",
                    shape=(10, 10, 10), tmp_folder=str(tmp_path / "tmp"),
                    config_dir=config_dir, max_jobs=1, target="inline")
    task.run()
    assert os.path.exists(mp)
    text = open(mp).read()
    assert "# TYPE ctt_stage_seconds_total counter" in text
    assert "# TYPE ctt_exec_cache_hit_ratio gauge" in text


# ---------------------------------------------------------------------------
# stage-name registry lint (satellite: typo'd stage buckets currently
# vanish silently into stage_counts)
# ---------------------------------------------------------------------------

def test_stage_literals_are_registered():
    """Thin shim (ISSUE 18): the PR-15 grep lint now lives in the
    unified ctt-lint runner as a real AST pass (analysis.registry),
    which additionally catches f-string/concatenated stage names the
    grep structurally could not.  Same test id, same guarantee."""
    from cluster_tools_tpu import analysis
    from cluster_tools_tpu.analysis import registry as areg

    report = analysis.run_analysis(passes=[areg.STAGE_PASS])
    bad = [f.format() for f in report["findings"]
           if f.rule == "stage-registry"]
    assert not bad, "\n".join(bad)
    # the canonical buckets the bench/docs rely on must actually be used
    src = "\n".join(open(p).read()
                    for p in analysis.sources.source_files())
    for name in ("sync-execute", "sync-compile", "store-write"):
        assert f'"{name}"' in src


def test_register_stage_extension():
    assert not telemetry.is_registered("ext-custom")
    try:
        telemetry.register_stage("ext-custom")
        assert telemetry.is_registered("ext-custom")
    finally:
        telemetry.STAGE_REGISTRY.discard("ext-custom")


# ---------------------------------------------------------------------------
# Prometheus writer
# ---------------------------------------------------------------------------

def test_prometheus_writer_format(tmp_path):
    path = str(tmp_path / "m.prom")
    telemetry.write_prometheus(path, [
        ("ctt_queue_depth", "gauge", "Requests waiting", [(None, 3)]),
        ("ctt_in_flight", "gauge", "Per-tenant in flight",
         [({"tenant": "alice"}, 2), ({"tenant": 'bo"b'}, 1)]),
    ])
    lines = open(path).read().splitlines()
    assert lines[0] == "# HELP ctt_queue_depth Requests waiting"
    assert lines[1] == "# TYPE ctt_queue_depth gauge"
    assert lines[2] == "ctt_queue_depth 3"
    assert 'ctt_in_flight{tenant="alice"} 2' in lines
    assert 'ctt_in_flight{tenant="bo\\"b"} 1' in lines     # escaped


# ---------------------------------------------------------------------------
# cumulative-bucket histograms (ISSUE 16 tentpole 2)
# ---------------------------------------------------------------------------

def test_histogram_cumulative_buckets_and_quantiles():
    h = telemetry.Histogram((0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(6.055)
    cum = h.cumulative()
    # Prometheus semantics: each le bucket counts ALL observations <= le
    assert cum == {"0.01": 1, "0.1": 2, "1.0": 4, "+Inf": 5}
    assert list(cum)[-1] == "+Inf"
    # monotone non-decreasing in le order
    vals = list(cum.values())
    assert vals == sorted(vals)
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(0.99)
    # p50 falls in the (0.1, 1.0] bucket
    assert 0.1 <= h.quantile(0.5) <= 1.0
    # quantiles beyond the finite buckets clamp to the highest bound
    assert h.quantile(1.0) == pytest.approx(1.0)


def test_histogram_boundary_observation_is_inclusive():
    h = telemetry.Histogram((1.0,))
    h.observe(1.0)                  # le="1.0" must include exactly 1.0
    assert h.cumulative() == {"1.0": 1, "+Inf": 1}


def test_histogram_merge_and_copy():
    a = telemetry.Histogram((0.1, 1.0))
    b = telemetry.Histogram((0.1, 1.0))
    a.observe(0.05)
    b.observe(0.5)
    c = a.copy()
    c.merge(b)
    assert a.count == 1             # copy is independent
    assert c.count == 2
    assert c.cumulative() == {"0.1": 1, "1.0": 2, "+Inf": 2}
    with pytest.raises(ValueError):
        a.merge(telemetry.Histogram((0.5,)))


def test_histogram_to_samples_prometheus_invariants(tmp_path):
    h = telemetry.Histogram((0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    samples = h.to_samples({"lane": "edit"})
    suffixes = [s[0] for s in samples]
    assert suffixes == ["_bucket", "_bucket", "_bucket", "_sum",
                        "_count"]
    les = [s[1]["le"] for s in samples[:3]]
    assert les == ["0.1", "1.0", "+Inf"]
    assert samples[2][2] == samples[4][2] == 3    # +Inf == _count
    # round-trip through the writer and the lint
    path = str(tmp_path / "h.prom")
    telemetry.write_prometheus(path, [telemetry.histogram_family(
        "ctt_server_request_latency_seconds", "Request latency",
        [({"lane": "edit"}, h)])])
    text = open(path).read()
    assert telemetry.lint_prometheus(text) == []
    assert 'ctt_server_request_latency_seconds_bucket' \
        '{lane="edit",le="+Inf"} 3' in text


# ---------------------------------------------------------------------------
# Prometheus text-format lint (promtool-style, satellite)
# ---------------------------------------------------------------------------

def _lint(text):
    return telemetry.lint_prometheus(text)


def test_lint_accepts_generated_snapshot(tmp_path):
    path = str(tmp_path / "ok.prom")
    h = telemetry.Histogram((0.5,))
    h.observe(0.1)
    telemetry.write_prometheus(path, [
        ("ctt_server_queue_depth", "gauge", "Depth", [(None, 2)]),
        ("ctt_server_in_flight", "gauge", "In flight",
         [({"tenant": 'a\\b"c'}, 1)]),            # escaping round-trips
        telemetry.histogram_family("ctt_server_queue_wait_seconds",
                                   "Wait", [(None, h)]),
    ] + telemetry.metrics_families())
    assert _lint(open(path).read()) == []


def test_lint_rejects_malformed_exposition():
    # sample with no TYPE
    assert _lint("ctt_x 1\n")
    # invalid metric name
    assert _lint("# TYPE 0bad gauge\n0bad 1\n")
    # invalid label syntax (unquoted value)
    assert _lint('# TYPE ctt_x gauge\nctt_x{l=a} 1\n')
    # bad escape in a label value
    assert _lint('# TYPE ctt_x gauge\nctt_x{l="a\\q"} 1\n')
    # non-float value
    assert _lint("# TYPE ctt_x gauge\nctt_x abc\n")
    # duplicate series
    assert _lint("# TYPE ctt_x gauge\nctt_x 1\nctt_x 2\n")
    # unknown TYPE
    assert _lint("# TYPE ctt_x wibble\nctt_x 1\n")


def test_lint_enforces_histogram_invariants():
    head = "# TYPE ctt_h histogram\n"
    # non-monotone cumulative buckets
    bad_mono = head + ('ctt_h_bucket{le="0.1"} 5\n'
                       'ctt_h_bucket{le="1.0"} 3\n'
                       'ctt_h_bucket{le="+Inf"} 5\n'
                       'ctt_h_sum 1\nctt_h_count 5\n')
    assert any("monoton" in e for e in _lint(bad_mono))
    # missing +Inf bucket
    bad_inf = head + ('ctt_h_bucket{le="0.1"} 1\n'
                      'ctt_h_sum 1\nctt_h_count 1\n')
    assert any("+Inf" in e for e in _lint(bad_inf))
    # +Inf disagrees with _count
    bad_count = head + ('ctt_h_bucket{le="+Inf"} 4\n'
                        'ctt_h_sum 1\nctt_h_count 5\n')
    assert any("_count" in e for e in _lint(bad_count))
    # missing _sum
    bad_sum = head + ('ctt_h_bucket{le="+Inf"} 1\nctt_h_count 1\n')
    assert any("_sum" in e for e in _lint(bad_sum))
    # a correct family passes
    good = head + ('ctt_h_bucket{le="0.1"} 1\n'
                   'ctt_h_bucket{le="+Inf"} 2\n'
                   'ctt_h_sum 0.3\nctt_h_count 2\n')
    assert _lint(good) == []


# ---------------------------------------------------------------------------
# metric-name registry lint (satellite: the stage-lint pattern extended
# to Prometheus family names)
# ---------------------------------------------------------------------------

def test_metric_literals_are_registered():
    """Thin shim (ISSUE 18): the PR-16 metric-name grep lint now lives
    in the unified ctt-lint runner as a real AST pass
    (analysis.registry), which additionally flags dynamic ``ctt_*``
    family names.  Same test id, same guarantee."""
    from cluster_tools_tpu import analysis
    from cluster_tools_tpu.analysis import registry as areg

    report = analysis.run_analysis(passes=[areg.METRIC_PASS])
    bad = [f.format() for f in report["findings"]
           if f.rule == "metric-registry"]
    assert not bad, "\n".join(bad)
    # the serve-path families PR 16 added must actually be emitted
    src = "\n".join(open(p).read()
                    for p in analysis.sources.source_files())
    for name in ("ctt_server_request_latency_seconds",
                 "ctt_slo_burn_rate",
                 "ctt_telemetry_dropped_spans_total"):
        assert f'"{name}"' in src


def test_dropped_span_counter_exported(fake_clock, tmp_path):
    """The ring's dropped-span count surfaces as a Prometheus counter
    (satellite: silent drops were invisible before)."""
    telemetry.configure(ring_size=4)
    for i in range(10):
        telemetry.record("host-map", float(i), float(i) + 0.5)
    path = str(tmp_path / "m.prom")
    telemetry.write_prometheus(path, telemetry.metrics_families())
    text = open(path).read()
    assert "# TYPE ctt_telemetry_dropped_spans_total counter" in text
    assert "ctt_telemetry_dropped_spans_total 6" in text
    assert "ctt_telemetry_ring_spans 4" in text
    assert _lint(text) == []


# ---------------------------------------------------------------------------
# trace-diff regression gate (ISSUE 16 tentpole 3)
# ---------------------------------------------------------------------------

_BASE_ROLLUPS = {
    "stage_seconds": {"sync-execute": 8.0, "h2d-upload": 0.6,
                      "host-solve": 2.0},
}


def test_diff_rollups_pass_path():
    """Candidate within thresholds (including small improvements): no
    regressions, exit-0 path."""
    cand = {
        "stage_seconds": {"sync-execute": 8.2, "h2d-upload": 0.5,
                          "host-solve": 2.2},   # host +10%: warning only
    }
    diff = telemetry.diff_rollups(_BASE_ROLLUPS, cand)
    assert diff["regressed"] is False
    assert diff["regressions"] == []
    assert diff["stages"]["sync-execute"]["regressed"] is False


def test_diff_rollups_host_regression_warns_not_gates():
    cand = dict(_BASE_ROLLUPS,
                stage_seconds={"sync-execute": 8.0, "h2d-upload": 0.6,
                               "host-solve": 9.0})
    diff = telemetry.diff_rollups(_BASE_ROLLUPS, cand)
    assert diff["regressed"] is False
    assert "stage:host-solve" in diff["warnings"]


def test_diff_rollups_abs_floor_ignores_micro_stages():
    base = {"stage_seconds": {"sync-execute": 0.001}}
    cand = {"stage_seconds": {"sync-execute": 0.01}}   # 10x, under the floor
    diff = telemetry.diff_rollups(base, cand)
    assert diff["regressed"] is False


def test_diff_rollups_new_stage_in_candidate_gates():
    """A device stage absent from the baseline is pure regression."""
    cand = dict(_BASE_ROLLUPS)
    cand = {**_BASE_ROLLUPS,
            "stage_seconds": {**_BASE_ROLLUPS["stage_seconds"],
                              "sync-meta": 1.0}}
    diff = telemetry.diff_rollups(_BASE_ROLLUPS, cand)
    assert "stage:sync-meta" in diff["regressions"]


def test_bench_trace_diff_cli_pass_and_fail(tmp_path):
    """End-to-end CLI: exit 0 on self-compare, nonzero on a synthetic
    device-stage regression (both paths of the acceptance criterion)."""
    import subprocess
    import sys as _sys

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = str(tmp_path / "base.json")
    regr = str(tmp_path / "regr.json")
    with open(base, "w") as f:
        json.dump({"rollups": _BASE_ROLLUPS}, f)
    cand = {**_BASE_ROLLUPS,
            "stage_seconds": {**_BASE_ROLLUPS["stage_seconds"],
                              "sync-execute": 12.0}}
    with open(regr, "w") as f:
        json.dump({"rollups": cand}, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(a, b):
        return subprocess.run(
            [_sys.executable, os.path.join(here, "bench.py"),
             "trace-diff", a, b],
            cwd=here, env=env, capture_output=True, text=True,
            timeout=120)

    ok = run(base, base)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert json.loads(ok.stdout)["regressed"] is False
    bad = run(base, regr)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    out = json.loads(bad.stdout)
    assert out["regressed"] is True
    assert "stage:sync-execute" in out["regressions"]
    assert out["stages"]["sync-execute"]["delta_s"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# correlation propagation (satellite: exemplar-style linking)
# ---------------------------------------------------------------------------

def test_correlation_scope_attaches_to_spans_and_records(fake_clock):
    with telemetry.correlation("aaaabbbbcccc"):
        assert telemetry.current_correlation() == "aaaabbbbcccc"
        with telemetry.span("work", cat="stage"):
            pass
        telemetry.record("host-map", 0.0, 1.0)
        with telemetry.correlation("ddddeeeeffff"):   # nesting: inner wins
            telemetry.record("host-map", 1.0, 2.0)
    telemetry.record("host-map", 2.0, 3.0)            # outside: no corr
    spans = telemetry.spans_snapshot()
    corr = [s.attrs.get("corr") for s in spans]
    assert corr == ["aaaabbbbcccc", "aaaabbbbcccc", "ddddeeeeffff",
                    None]
    assert telemetry.current_correlation() is None


def test_correlation_explicit_attr_not_overwritten(fake_clock):
    with telemetry.correlation("aaaabbbbcccc"):
        telemetry.record("host-map", 0.0, 1.0, corr="explicit")
    (s,) = telemetry.spans_snapshot()
    assert s.attrs["corr"] == "explicit"


def test_correlation_in_chrome_trace_args(fake_clock, tmp_path):
    """The join key lands in the exported Chrome-trace args, so a
    histogram outlier joins back to its Perfetto spans."""
    with telemetry.correlation("abc123def456"):
        with telemetry.span("attempt", cat="attempt"):
            telemetry.record_stage("sync-execute", 0.5)
    path = str(tmp_path / "t.json")
    telemetry.export_chrome_trace(path)
    with open(path) as f:
        xs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert e["args"]["corr"] == "abc123def456"


def test_retry_attempt_children_inherit_correlation(tmp_path):
    """End-to-end: worker-thread job/stage spans recorded inside a
    retried task's attempts carry the attempt's 12-hex id in attrs —
    the correlation stack is process-global on purpose."""
    config_dir = str(tmp_path / "configs")
    ConfigDir(config_dir).write_global_config(
        {"block_shape": [10, 10, 10], "max_num_retries": 2,
         "telemetry_enabled": True})
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir)
    task = FailingTask(output_path=str(tmp_path / "out.n5"),
                       output_key="data", shape=(20, 20, 20),
                       tmp_folder=str(tmp_path / "t"),
                       config_dir=config_dir, max_jobs=4,
                       target="threads")
    orig = task.run_jobs

    def run_jobs(block_list, cfg, **kw):
        return orig(block_list, {**cfg, "marker_dir": marker_dir}, **kw)

    task.run_jobs = run_jobs
    task.run()
    spans = telemetry.spans_snapshot()
    attempts = [s for s in spans if s.cat == "attempt"]
    (corr,) = {s.attrs["correlation_id"] for s in attempts}
    assert re.fullmatch(r"[0-9a-f]{12}", corr)
    jobs = [s for s in spans if s.cat == "job"]
    assert jobs
    for j in jobs:
        assert j.attrs.get("corr") == corr, j


# ---------------------------------------------------------------------------
# telemetry-off overhead gate (CI satellite: wired into tier-1)
# ---------------------------------------------------------------------------

def test_telemetry_off_overhead_under_one_percent():
    """The <1% wall gate as a projection: measured per-call cost of a
    DISABLED stage_add (the only thing a telemetry-off run pays), times
    the flagship's total stage entries, against 1% of the recorded
    telemetry-off wall.  Reads the committed TRACE_r07.json when present
    so the gate tracks the real artifact; nominal fallback otherwise."""
    assert not telemetry.enabled()
    n_entries, wall_off = 101, 9.0                # TRACE_r07 nominal
    trace = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "TRACE_r07.json")
    if os.path.exists(trace):
        with open(trace) as f:
            doc = json.load(f)
        n_entries = doc["stage_entries"]
        wall_off = doc["wall_off_s"]
    n_cal = 50_000
    t0 = time.perf_counter()
    for _ in range(n_cal):
        runtime.stage_add("host-map", 0.0)
    per_call = (time.perf_counter() - t0) / n_cal
    projected = per_call * n_entries
    assert projected < 0.01 * wall_off, (
        f"telemetry-off overhead projection {projected:.6f}s exceeds 1% "
        f"of the {wall_off}s flagship wall ({per_call * 1e9:.0f} ns/call "
        f"x {n_entries} entries)")

# ---------------------------------------------------------------------------
# memory observability (ISSUE 17 tentpole a: probe, counter tracks,
# per-span watermarks, memory rollup)
# ---------------------------------------------------------------------------

def test_host_memory_probe_reads_proc_status():
    """The probe reads real, positive RSS/HWM bytes and the shared
    peak-RSS helper uses the 1024-based conversion (the old ad-hoc
    ``ru_maxrss / 1e6`` it replaces OVERSTATES GiB, so the bench's
    ``< 7 GB`` bound only got safer)."""
    import resource

    mem = telemetry.host_memory_bytes()
    assert mem["rss"] > 0 and mem["hwm"] > 0
    gib = telemetry.host_peak_rss_gb()
    assert gib == pytest.approx(mem["hwm"] / 1024.0 ** 3)
    old_style = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    assert gib < old_style + 1e-9


def test_device_memory_probe_graceful_without_allocator_stats():
    """CPU jaxlib exposes no allocator stats: the device probe returns
    None (never raises) and NEVER imports jax as a side effect."""
    dev = telemetry.device_memory_bytes()
    assert dev is None or (dev["in_use"] >= 0 and
                           dev["peak"] >= dev["in_use"])


def test_sample_memory_exports_counter_tracks(fake_clock, tmp_path):
    """Counter samples export as Chrome 'C' events (one Perfetto counter
    track per series) and stay OFF the thread-metadata tracks."""
    telemetry.sample_memory()
    with telemetry.span("block:0", cat="block", block=0):
        pass
    path = str(tmp_path / "trace.json")
    telemetry.export_chrome_trace(path, telemetry.spans_snapshot())
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"]
    assert {e["name"] for e in counters} >= {"host_rss_gb",
                                            "host_hwm_gb"}
    for e in counters:
        assert set(e["args"]) == {"value"}
        assert e["args"]["value"] > 0
    # the counter pseudo-track claims no thread-name metadata
    thread_meta_tids = {e["tid"] for e in events
                       if e["ph"] == "M" and e["name"] == "thread_name"}
    assert not any(e["tid"] in thread_meta_tids for e in counters)
    # the block span still exports as a normal 'X' slice
    assert any(e["ph"] == "X" and e["name"] == "block:0"
               for e in events)


def test_sample_memory_disabled_is_noop():
    assert not telemetry.enabled()
    assert telemetry.sample_memory() is None
    telemetry.annotate_memory(telemetry.span("x"))   # null span: no-op
    assert telemetry.spans_snapshot() == []


def test_annotate_memory_stamps_span_watermarks(fake_clock):
    """Drain-point hook: mem_* attrs land on the open span and the
    rollup folds them into per-span-name watermarks + the peak scalars
    the trace-diff gate compares."""
    with telemetry.span("block:3", cat="block", block=3) as sp:
        telemetry.annotate_memory(sp)
    roll = telemetry.memory_rollup()
    wm = roll["span_watermarks"]["block:3"]
    assert wm["mem_host_rss_gb"] > 0
    assert wm["mem_host_hwm_gb"] > 0
    assert roll["peak_host_rss_gb"] >= wm["mem_host_rss_gb"]
    assert roll["counters"]["host_rss_gb"]["n"] == 1
    # summary() embeds the same rollup (bench artifacts record it)
    assert telemetry.summary()["memory"]["peak_host_rss_gb"] \
        == roll["peak_host_rss_gb"]


def test_memory_rollup_empty_trace_has_null_peaks():
    """A trace with no memory samples yields None peaks — the
    degrade-to-skip contract diff_rollups depends on."""
    roll = telemetry.memory_rollup([])
    assert roll["peak_host_rss_gb"] is None
    assert roll["peak_device_gb"] is None
    assert roll["counters"] == {} and roll["span_watermarks"] == {}


def test_memory_sampler_background_thread(fake_clock):
    """The optional background probe records counter samples while
    running and stops cleanly."""
    with telemetry.MemorySampler(interval_s=0.005):
        deadline = time.time() + 2.0
        while telemetry.memory_rollup()["counters"].get(
                "host_rss_gb", {}).get("n", 0) < 2:
            assert time.time() < deadline, "sampler recorded nothing"
            time.sleep(0.005)
    n = telemetry.memory_rollup()["counters"]["host_rss_gb"]["n"]
    time.sleep(0.02)      # stopped: no further samples
    assert telemetry.memory_rollup()["counters"]["host_rss_gb"]["n"] == n


# ---------------------------------------------------------------------------
# trace-diff memory gate + malformed/partial artifacts (satellite 3)
# ---------------------------------------------------------------------------

_MEM_ROLLUPS = {**_BASE_ROLLUPS,
                "memory": {"peak_host_rss_gb": 4.0,
                           "peak_device_gb": 2.0}}


def test_diff_rollups_memory_regression_gates():
    """A synthetic peak-HBM regression fails the gate exactly like a
    device-busy regression (acceptance criterion)."""
    cand = {**_MEM_ROLLUPS,
            "memory": {"peak_host_rss_gb": 4.0, "peak_device_gb": 3.5}}
    diff = telemetry.diff_rollups(_MEM_ROLLUPS, cand)
    assert diff["regressed"] is True
    assert diff["regressions"] == ["memory:peak_device_gb"]
    assert diff["memory"]["peak_device_gb"]["delta_gb"] \
        == pytest.approx(1.5)
    # self-compare passes
    ok = telemetry.diff_rollups(_MEM_ROLLUPS, _MEM_ROLLUPS)
    assert ok["regressed"] is False


def test_diff_rollups_memory_abs_floor_and_threshold():
    """Small absolute growth under the GiB floor never regresses; the
    floor is configurable like the seconds floor."""
    cand = {**_MEM_ROLLUPS,
            "memory": {"peak_host_rss_gb": 4.2, "peak_device_gb": 2.0}}
    assert telemetry.diff_rollups(
        _MEM_ROLLUPS, cand)["regressed"] is False      # +0.2 < 1.0 rel floor
    tight = telemetry.diff_rollups(_MEM_ROLLUPS, cand,
                                   mem_abs_floor_gb=0.05,
                                   rel_threshold=0.01)
    assert "memory:peak_host_rss_gb" in tight["regressions"]


def test_diff_rollups_baseline_without_memory_skips():
    """Pre-memory baselines (e.g. the committed TRACE_r07) degrade to
    skipping the memory checks — never a crash or false regression."""
    diff = telemetry.diff_rollups(_BASE_ROLLUPS, _MEM_ROLLUPS)
    assert diff["regressed"] is False
    assert diff["memory"]["peak_host_rss_gb"]["skipped"] is True
    rev = telemetry.diff_rollups(_MEM_ROLLUPS, _BASE_ROLLUPS)
    assert rev["regressed"] is False


def test_diff_rollups_malformed_artifacts_never_crash():
    """Satellite 3: missing rollup keys, empty span lists, wrong-typed
    sections and junk values all degrade to skip/zero, keeping the
    trace-diff gate alive."""
    cases = [
        {}, {"stage_seconds": None}, {"stage_seconds": "junk"},
        {"memory": "junk"}, {"memory": {"peak_host_rss_gb": "junk"}},
        {"stage_seconds": {"sync-execute": "junk"},
         "device_busy_s": None, "pipeline_bubble_frac": "junk",
         "memory": {"peak_host_rss_gb": None}},
        telemetry.rollup_spans([]),      # empty trace, real shape
    ]
    for a in cases:
        for b in cases:
            diff = telemetry.diff_rollups(a, b)
            assert diff["regressed"] is False, (a, b, diff)


# ---------------------------------------------------------------------------
# cross-process trace shards + merge (ISSUE 17 tentpole c)
# ---------------------------------------------------------------------------

def test_trace_shard_roundtrip(fake_clock, tmp_path):
    with telemetry.span("block:0", cat="block", block=0) as sp:
        telemetry.annotate_memory(sp)
    path = str(tmp_path / "trace_shard_p0.json")
    n = telemetry.export_trace_shard(path, process_index=0,
                                     process_count=2,
                                     wall_anchor=100.0, perf_anchor=1.0)
    sh = telemetry.load_trace_shard(path)
    assert sh["process_index"] == 0 and sh["process_count"] == 2
    assert sh["wall_anchor"] == 100.0 and sh["perf_anchor"] == 1.0
    assert len(sh["spans"]) == n >= 2          # block span + counter


def _synthetic_shard(path, pidx, wall_anchor, perf_anchor, spans):
    doc = {"process_index": pidx, "process_count": 2,
           "wall_anchor": wall_anchor, "perf_anchor": perf_anchor,
           "dropped": 0,
           "spans": [{"sid": i + 1, "parent": None, "name": n,
                      "cat": c, "t0": t0, "t1": t1, "tid": 1,
                      "tname": "MainThread", "attrs": a}
                     for i, (n, c, t0, t1, a) in enumerate(spans)]}
    with open(path, "w") as f:
        json.dump(doc, f)


def test_merge_chrome_traces_rebases_and_remaps(tmp_path):
    """Two shards with different clock origins merge into ONE trace:
    pids remapped per process, timestamps rebased through the
    barrier-aligned anchors, and the merged rollups aggregate stage
    seconds across the mesh (span counts cross-checked per process)."""
    p0 = str(tmp_path / "trace_shard_p0.json")
    p1 = str(tmp_path / "trace_shard_p1.json")
    # process 0: perf clock starts at 1000; process 1: at 5; their wall
    # anchors differ by 0.5 s (process 1 reached the barrier later)
    _synthetic_shard(p0, 0, 100.0, 1000.0, [
        ("sync-execute", "stage", 1000.0, 1000.5,
         {"mem_dev_peak_gb": 1.0}),
        ("host-map", "stage", 1000.5, 1000.6, {})])
    _synthetic_shard(p1, 1, 100.5, 5.0, [
        ("sync-execute", "stage", 5.0, 5.25, {"mem_dev_peak_gb": 2.0})])
    out = str(tmp_path / "merged.json")
    m = telemetry.merge_chrome_traces([p1, p0], out)   # order-insensitive
    assert m["n_processes"] == 2
    assert [p["pid"] for p in m["processes"]] == [1, 2]
    assert [p["clock_offset_s"] for p in m["processes"]] == [0.0, 0.5]
    assert {p["process_index"]: p["n_spans"]
            for p in m["processes"]} == {0: 2, 1: 1}
    assert m["rollups"]["n_spans"] == 3
    assert m["rollups"]["stage_seconds"]["sync-execute"] == \
        pytest.approx(0.75)
    assert m["rollups"]["memory"]["peak_device_gb"] == pytest.approx(2.0)
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    assert {e["pid"] for e in events} == {1, 2}
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    # p1's span started 0.5 s into p0's timeline after the wall rebase:
    # (5.0 - 5.0) + (100.5 - 100.0) -> +0.5 s from the trace base
    assert xs["sync-execute"]["ts"] in (0, 500_000)
    assert all(e["ts"] >= 0 for e in events if "ts" in e)
    # merged trace is a loadable Chrome trace: every event well-formed
    assert all({"ph", "pid", "name"} <= set(e) for e in events)


def test_merge_chrome_traces_empty_raises(tmp_path):
    with pytest.raises(ValueError):
        telemetry.merge_chrome_traces([], str(tmp_path / "out.json"))


# ---------------------------------------------------------------------------
# crash flight recorder (ISSUE 17 tentpole d)
# ---------------------------------------------------------------------------

def test_flight_record_dump_contents(fake_clock, tmp_path):
    """The dump carries the span ring, a live memory probe + rollup, the
    process identity and caller-supplied correlation state — written
    atomically (no .tmp litter)."""
    with telemetry.correlation("req_42"):
        with telemetry.span("block:0", cat="block", block=0) as sp:
            telemetry.annotate_memory(sp)
    path = telemetry.flight_record(
        str(tmp_path), "tenant-fault:req_42",
        extra={"request": "req_42", "tenant": "alice"})
    assert os.path.basename(path).startswith("flightrec_tenant-fault")
    assert not [p for p in os.listdir(str(tmp_path)) if ".tmp" in p]
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "tenant-fault:req_42"
    assert doc["extra"] == {"request": "req_42", "tenant": "alice"}
    assert doc["n_spans"] == len(doc["spans"]) >= 2
    assert any(s["attrs"].get("corr") == "req_42" for s in doc["spans"])
    assert doc["memory"]["probe"]["host"]["rss"] > 0
    assert doc["memory"]["rollup"]["peak_host_rss_gb"] > 0
    assert doc["process_count"] >= 1
    assert telemetry.flight_record_count() == 1
    # the counter surfaces in the Prometheus families
    fams = {f[0]: f for f in telemetry.metrics_families()}
    assert fams["ctt_telemetry_flight_records_total"][3] == [(None, 1)]


def test_flight_record_works_with_telemetry_disabled(tmp_path):
    assert not telemetry.enabled()
    path = telemetry.flight_record(str(tmp_path), "sigterm")
    with open(path) as f:
        doc = json.load(f)
    assert doc["n_spans"] == 0 and doc["spans"] == []
    assert doc["memory"]["probe"]["host"]["hwm"] > 0


def test_install_flight_recorder_chains_and_uninstalls(tmp_path):
    """The excepthook wrapper dumps a record, then CHAINS the previous
    hook; uninstall restores it exactly."""
    import sys as _sys

    seen = []
    prev = _sys.excepthook
    _sys.excepthook = lambda *a: seen.append(a)
    try:
        uninstall = telemetry.install_flight_recorder(
            str(tmp_path), extra_fn=lambda: {"stage": "serve"})
        try:
            err = ValueError("boom")
            _sys.excepthook(ValueError, err, None)
        finally:
            uninstall()
        assert _sys.excepthook is not prev
        assert len(seen) == 1 and seen[0][1] is err
        recs = [p for p in os.listdir(str(tmp_path))
                if p.startswith("flightrec_")]
        assert len(recs) == 1
        with open(os.path.join(str(tmp_path), recs[0])) as f:
            doc = json.load(f)
        assert doc["reason"] == "exception"
        assert doc["extra"]["exc_type"] == "ValueError"
        assert doc["extra"]["stage"] == "serve"
    finally:
        _sys.excepthook = prev


# ---------------------------------------------------------------------------
# profiler sink: program spans in a JAX profiler trace, on the thread that
# does the work
# ---------------------------------------------------------------------------

class _StepTime:
    """Stand-in for ``runtime.time``: ``perf_counter`` advances a fixed
    step per call, everything else is the real module."""

    def __init__(self, step):
        self.perf_counter = FakeClock(step)

    def __getattr__(self, name):
        return getattr(time, name)


def _host_lines(trace_dir):
    """{line index: [event names]} of the host plane of the trace the
    profiler wrote under ``trace_dir`` (one line per OS thread)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    (plane,) = [p for p in pd.planes if p.name == "/host:CPU"]
    return {i: [ev.name for ev in line.events]
            for i, line in enumerate(plane.lines)}


@pytest.fixture(scope="module")
def profiled_lines(tmp_path_factory):
    """One CPU profiler trace holding a ``runtime.stage`` block and a
    ``telemetry.span`` on the main thread and a ``BoundedPool`` task on
    its worker thread; each site also opens a plain marker annotation on
    its own thread."""
    import jax
    from jax.profiler import TraceAnnotation

    trace_dir = str(tmp_path_factory.mktemp("profile"))

    def pooled():
        with TraceAnnotation("marker.pool"):
            pass

    jax.profiler.start_trace(trace_dir)
    try:
        with runtime.stage("host-map"):
            with TraceAnnotation("marker.stage"):
                pass
        with telemetry.span("fill", cat="attempt", n_jobs=1):
            with TraceAnnotation("marker.span"):
                pass
        with runtime.BoundedPool(1) as pool:
            pool.submit(pooled)
    finally:
        jax.profiler.stop_trace()
    return _host_lines(trace_dir)


@pytest.mark.parametrize("site, event", [
    ("stage", "ctt.stage.host-map"),
    ("span", "ctt.attempt.fill"),
    ("pool", "ctt.pool.pool:pooled"),
])
def test_profiler_sink_spans_on_the_working_thread(profiled_lines, site,
                                                   event):
    """Whenever the profiler records, stages, spans and pool tasks are
    ``ctt.*`` events of the trace, on the line of the thread that ran
    them (the pool task on its worker, not on the submitting thread);
    the ring stays off."""
    line = {name: i for i, names in profiled_lines.items()
            for name in names}
    assert event in line, profiled_lines
    assert line[event] == line[f"marker.{site}"]
    if site == "pool":
        assert line[event] != line["marker.stage"]
    assert telemetry.spans_snapshot() == []


def test_profiler_off_records_nothing_and_keeps_accumulators(tmp_path,
                                                             monkeypatch):
    """With no profiler trace recording, a stage or span opens no
    annotation (the span is the shared no-op), and the accumulators read
    what a traced run of the same calls reads; the traced run's trace
    holds exactly its program spans."""
    import jax

    monkeypatch.setattr(runtime, "time", _StepTime(0.25))
    assert not telemetry.profiling() and not telemetry.tracing()
    assert telemetry.open_annotation("stage.host-map") is None
    assert telemetry.span("w", cat="job") is telemetry.span("x")

    def work():
        with runtime.stage("host-map"):
            pass
        with telemetry.span("w", cat="job"):
            with runtime.stage("store-write"):
                runtime.stage_bytes("store-write", 64)

    trace_dir = str(tmp_path / "on")
    deltas = []
    for traced in (True, False):
        st0 = runtime.stages_snapshot()
        cn0 = runtime.counts_snapshot()
        by0 = runtime.bytes_snapshot()
        if traced:
            jax.profiler.start_trace(trace_dir)
        try:
            work()
        finally:
            if traced:
                jax.profiler.stop_trace()
        deltas.append((runtime.stages_delta(st0), runtime.counts_delta(cn0),
                       runtime.bytes_delta(by0)))
    (on_s, on_c, on_b), (off_s, off_c, off_b) = deltas
    assert on_c == off_c == {"host-map": 1, "store-write": 1}
    assert on_b == off_b == {"store-write": 64.0}
    assert on_s == pytest.approx(off_s)
    assert off_s == pytest.approx({"host-map": 0.25, "store-write": 0.25})
    events = sorted(n for names in _host_lines(trace_dir).values()
                    for n in names if n.startswith(telemetry.PROFILER_PREFIX))
    assert events == ["ctt.job.w", "ctt.stage.host-map",
                      "ctt.stage.store-write"]
    assert telemetry.spans_snapshot() == []
