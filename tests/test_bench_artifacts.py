"""Committed bench/trace artifact hygiene (ISSUE 17 satellite 5).

Two guards against artifact drift, both cheap enough for tier-1:

* the trace-diff gate runs IN-PROCESS against the committed TRACE
  artifact — a self-diff must exit 0 (and a synthetic peak-memory
  regression must exit 1), so `bench.py trace-diff TRACE_r07.json <new>`
  stays trustworthy for every perf PR;
* every committed ``BENCH_*.json`` / ``TRACE_*.json`` lints against a
  minimal schema (parseable JSON, recognizable identity keys, rollup
  and trace-event invariants), so a hand-edited or truncated artifact
  is caught at test time instead of at the next trace-diff run.
"""

import json
import os

import pytest

import bench
from cluster_tools_tpu.analysis import sources
from cluster_tools_tpu.core import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_R07 = os.path.join(REPO, "TRACE_r07.json")


def _run_trace_diff(argv):
    with pytest.raises(SystemExit) as exc:
        bench.main_trace_diff(argv)
    return exc.value.code


def test_trace_diff_self_diff_exits_zero(capsys):
    """The acceptance criterion's pass path, in-process: comparing the
    committed TRACE artifact against itself finds no regressions."""
    assert os.path.exists(TRACE_R07), "committed TRACE_r07.json missing"
    assert _run_trace_diff([TRACE_R07, TRACE_R07]) == 0
    diff = json.loads(capsys.readouterr().out)
    assert diff["regressed"] is False and diff["regressions"] == []


def test_trace_diff_synthetic_memory_regression_exits_nonzero(
        tmp_path, capsys):
    """The acceptance criterion's fail path: a candidate whose peak
    device memory grew past the floor exits nonzero through the same
    CLI entry point (and the floor is flag-tunable)."""
    with open(TRACE_R07) as f:
        rollups = json.load(f)["rollups"]
    base = dict(rollups, memory={"peak_host_rss_gb": 2.0,
                                 "peak_device_gb": 4.0})
    regr = dict(rollups, memory={"peak_host_rss_gb": 2.0,
                                 "peak_device_gb": 8.0})
    bp, rp = str(tmp_path / "base.json"), str(tmp_path / "regr.json")
    with open(bp, "w") as f:
        json.dump({"rollups": base}, f)
    with open(rp, "w") as f:
        json.dump({"rollups": regr}, f)
    assert _run_trace_diff([bp, rp]) == 1
    diff = json.loads(capsys.readouterr().out)
    assert "memory:peak_device_gb" in diff["regressions"]
    # widen the memory floor past the delta: the gate opens
    assert _run_trace_diff([bp, rp, "--mem-abs-floor-gb", "10"]) == 0
    capsys.readouterr()


def test_trace_diff_accepts_pre_memory_baseline(tmp_path, capsys):
    """A baseline WITHOUT memory fields (the pre-ISSUE-17 artifact
    format) degrades to skipping the memory checks — satellite 3's
    contract holds end-to-end through the CLI."""
    with open(TRACE_R07) as f:
        rollups = json.load(f)["rollups"]
    cand = dict(rollups, memory={"peak_host_rss_gb": 2.0,
                                 "peak_device_gb": 4.0})
    old = {k: v for k, v in rollups.items() if k != "memory"}
    bp, cp = str(tmp_path / "old.json"), str(tmp_path / "new.json")
    with open(bp, "w") as f:
        json.dump({"rollups": old}, f)
    with open(cp, "w") as f:
        json.dump({"rollups": cand}, f)
    assert _run_trace_diff([bp, cp]) == 0
    diff = json.loads(capsys.readouterr().out)
    assert diff["memory"]["peak_device_gb"]["skipped"] is True


# ---------------------------------------------------------------------------
# minimal schema lint over every committed artifact
# ---------------------------------------------------------------------------

#: keys that identify a bench artifact generation (one must be present)
_BENCH_IDENTITY_KEYS = ("metric", "config", "cmd")


def _committed(pattern):
    # delegates to the shared analysis.sources walker (ISSUE 18 satellite
    # 6) so "what counts as a committed artifact" has one definition
    return sources.committed_artifacts(pattern)


def test_committed_artifacts_exist():
    assert _committed("BENCH_*.json"), "no committed BENCH artifacts?"
    assert _committed("TRACE_*.json"), "no committed TRACE artifacts?"


@pytest.mark.parametrize("path", _committed("BENCH_*.json"),
                         ids=os.path.basename)
def test_bench_artifact_schema(path):
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc, dict) and doc, path
    assert any(k in doc for k in _BENCH_IDENTITY_KEYS), \
        f"{os.path.basename(path)}: no identity key " \
        f"{_BENCH_IDENTITY_KEYS} — unrecognizable artifact"
    # artifacts that embed a memory rollup must use the canonical shape
    if isinstance(doc.get("memory"), dict):
        assert set(doc["memory"]) >= {"peak_host_rss_gb",
                                      "peak_device_gb"}, path


def test_bench_edits_artifact_schema():
    """BENCH_edits.json (ISSUE 19): the committed proofreading artifact
    carries the acceptance-criteria evidence — round-trip vs full-solve
    ratio under 0.5, per-lane queue-wait histograms showing edits not
    starved, and the incremental == from-scratch identity gate."""
    paths = _committed("BENCH_edits.json")
    assert paths, "BENCH_edits.json not committed"
    with open(paths[0]) as f:
        doc = json.load(f)
    assert doc["metric"] == "edit_roundtrip"
    assert doc["full_solve_s"] > 0
    assert 0 < doc["median_edit_round_trip_s"] <= \
        doc["p90_edit_round_trip_s"]
    assert doc["round_trip_over_full_solve"] < 0.5
    assert doc["identity_incremental_equals_scratch"] is True
    assert doc["gates"] == {"ratio_lt_0_5": True,
                            "edit_not_starved": True, "identity": True}
    assert len(doc["edits"]) >= 5
    for e in doc["edits"]:
        assert e["op"] in ("merge", "split")
        assert e["round_trip_s"] > 0 and e["affected_blocks"] >= 1
    qw = doc["queue_wait"]
    assert qw["edit_p50_s"] <= qw["bulk_p50_s"]
    for lane in ("edit", "bulk"):
        hist = qw[lane]
        assert hist["+Inf"] == max(hist.values())    # cumulative buckets
    c = doc["counters"]
    assert c["applied"] == len(doc["edits"])
    assert c["warm_reused"] > 0 and c["fallback"] == 0
    assert doc["bulk_requests_served"] > 0


@pytest.mark.parametrize("path",
                         [p for p in _committed("TRACE_*.json")
                          if not p.endswith("_trace.json")],
                         ids=os.path.basename)
def test_trace_artifact_schema(path):
    """Rollup-bearing TRACE artifacts: the fields the trace-diff gate
    reads must exist and parse."""
    with open(path) as f:
        doc = json.load(f)
    assert any(k in doc for k in _BENCH_IDENTITY_KEYS), path
    roll = doc.get("rollups")
    assert isinstance(roll, dict), path
    assert isinstance(roll.get("stage_seconds"), dict), path
    # the gate itself must accept the artifact (self-diff, in-library)
    diff = telemetry.diff_rollups(roll, roll)
    assert diff["regressed"] is False


@pytest.mark.parametrize("path", _committed("TRACE_*_trace.json"),
                         ids=os.path.basename)
def test_chrome_trace_artifact_schema(path):
    """Chrome-trace artifacts: a traceEvents list of well-formed events
    (what Perfetto actually loads)."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events, path
    for e in events:
        assert {"ph", "name", "pid"} <= set(e), e
        if e["ph"] in ("X", "C"):
            assert e["ts"] >= 0, e
        if e["ph"] == "X":
            assert e["dur"] >= 0, e
        if e["ph"] == "C":
            assert "value" in e["args"], e
