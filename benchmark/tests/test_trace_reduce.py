"""The trace reduction, checked against a small trace recorded on the chip
(``data/edt_trace.xplane.pb``: PR 22, one TPU v5 lite; three calls of the
program's Pallas EDT at (16, 128, 128) and one XLA cumsum, a 50 ms sleep
between them, inside the
host spans ``bench.chain0`` and ``bench.chain1``)."""

import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "edt_trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(trace_reduce.load(FIXTURE), n_devices=1)


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        [0, 3], [5, 9]]


def test_reduces_to_fixed_numbers(red):
    assert red["n_devices"] == 1
    assert red["n_events"] == EXPECTED["n_events"]
    assert red["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert red["custom_call_s"] == pytest.approx(EXPECTED["custom_call_s"],
                                                 rel=1e-9)
    assert 0 < red["custom_call_s"] < red["busy_s"]
    assert [n for n, _ in red["top_ops"]][:2] == EXPECTED["top_ops"]


def test_gaps_are_labelled_by_bench_spans(red):
    gaps = trace_reduce.label_gaps(red)
    assert gaps and all(s > 0 for _, s in gaps)
    # the 50 ms sleep inside bench.chain0 is the longest idle gap
    assert gaps[0][0] == "bench.chain0"
    assert gaps[0][1] == pytest.approx(0.05, abs=0.02)


EXPECTED = {
    "n_events": 31,
    "busy_s": 0.0021656730000000003,
    "custom_call_s": 0.0020865790000000003,
    "top_ops": ["jit__lambda/_edt_impl.3 custom-call",
                "jit__lambda/_edt_impl.4 custom-call"],
}


def test_idle_gaps_cover_the_chain_and_cut_at_tasks():
    merged = [[10, 20], [30, 40]]
    spans = [("bench.chain0", 0, 100), ("bench.task.a", 0, 35),
             ("bench.task.b", 35, 100)]
    gaps = trace_reduce.idle_gaps(merged, spans)
    assert sorted(gaps) == [(0, 10), (20, 30), (40, 100)]
    red = {"gaps_ns": gaps, "spans": spans}
    labels = dict((round(s * 1e9), name) for name, s in
                  ((n, v) for n, v in trace_reduce.label_gaps(red)))
    assert labels[60] == "bench.task.b" and labels[10] == "bench.task.a"
