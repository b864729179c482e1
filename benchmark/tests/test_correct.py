"""``correct`` must fail where it should.

* The control, through the harness's own decision: once the window has
  closed, the bfloat16 reference's outputs are written into each chain's
  store in the program's place (``benchmark/control.py``), on three seeds.
* The faults a cell can have, planted under the timed path while the rest
  of a run (set-up, window, comparison) runs as on the chip, with the
  harness's look for a chip skipped: a block's answer altered where it is
  produced (its two largest fragments merged), half of the blocks left out
  (their labels never computed), and a step that returns its state
  unchanged (each block handed the previous block's answer); in the
  multicut cell also the edge costs negated before the solve, the two
  largest segments merged in the solver's assignment table, and the final
  segmentation write skipped.  One chip and no mesh: no exchange between
  chips to leave out.

Small sizes on the CPU: (64, 256, 256) in [32, 128, 128] blocks, every
block compared.  The CPU runs the program's convolutions in float32, so
the reference does too here (``conv_operands``).
"""

import importlib
import threading

import numpy as np
import pytest

import control
import run

CELLS = ["cremi_a_watershed.clean", "cremi_a_multicut.clean"]
SEED = 2 ** 33 + 101


def small(cell):
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    wl, cfg, mix, per_layer = run.resolve(bench, cell)
    cfg = dict(cfg, shape=[64, 256, 256],
               global_config=dict(cfg["global_config"],
                                  block_shape=[32, 128, 128]))
    ref = cfg["reference"]
    cfg["reference"] = dict(ref, sample_blocks=8, params=dict(
        ref["params"], conv_operands="float32"))
    return wl, cfg, mix


def run_small(cell, seed=SEED):
    wl, cfg, mix = small(cell)
    return run.execute(cell, wl, cfg, mix, [], seed, 0.1, 0,
                       require_chip=False)


def merge_two_largest(labels):
    ids, counts = np.unique(labels[labels > 0], return_counts=True)
    if len(ids) < 2:
        return labels
    a, b = ids[np.argsort(-counts)[:2]]
    return np.where(labels == b, a, labels)


FRAGMENT_FAULTS = {
    "altered": lambda i, lab, prev: merge_two_largest(lab),
    "half_left_out": lambda i, lab, prev: (np.zeros_like(lab) if i % 2
                                           else lab),
    "state_unchanged": lambda i, lab, prev: lab if prev is None else prev,
}
SEGMENT_FAULTS = ("costs_negated", "segments_merged", "write_skipped")


def plant(monkeypatch, cell, fault):
    """Break the timed path from the window's first chain on (the warm-up
    chain runs sound); returns a dict whose ``hits`` counts the faults
    made."""
    state = {"hits": 0, "prev": None, "chains": 0}
    lock = threading.Lock()
    run_chain = run.run_chain

    def counted(*a, **kw):
        state["chains"] += 1
        return run_chain(*a, **kw)

    monkeypatch.setattr(run, "run_chain", counted)

    def window():
        return state["chains"] >= 2

    def apply(lab):
        if not window():
            return lab
        with lock:
            out = FRAGMENT_FAULTS[fault](state["hits"], lab, state["prev"])
            state["hits"] += 1
            state["prev"] = lab
        return out

    if fault == "costs_negated":
        from cluster_tools_tpu.workflows import costs

        orig_costs = costs.transform_probabilities_to_costs

        def negated(*a, **kw):
            c = orig_costs(*a, **kw)
            if window():
                state["hits"] += 1
                return -c
            return c

        monkeypatch.setattr(costs, "transform_probabilities_to_costs",
                            negated)
    elif fault == "segments_merged":
        from cluster_tools_tpu.workflows import multicut

        orig_save = multicut.save_assignment_table

        def merged(nodes, labels, path):
            if window():
                state["hits"] += 1
                ids, counts = np.unique(labels, return_counts=True)
                a, b = ids[np.argsort(-counts)[:2]]
                labels = np.where(labels == b, a, labels)
            return orig_save(nodes, labels, path)

        monkeypatch.setattr(multicut, "save_assignment_table", merged)
    elif fault == "write_skipped":
        from cluster_tools_tpu.workflows import write

        orig_job = write.WriteAssignments.process_job

        def skipped(cls, job_id, job_config, log_fn):
            if window():
                state["hits"] += 1
                return None
            return orig_job(job_id, job_config, log_fn)

        monkeypatch.setattr(write.WriteAssignments, "process_job",
                            classmethod(skipped))
    elif cell.startswith("cremi_a_watershed"):
        from cluster_tools_tpu.workflows import watershed

        orig = watershed.iter_ws_blocks_stream

        def broken(blocks, cfg):
            for ws in orig(blocks, cfg):
                yield apply(ws)

        monkeypatch.setattr(watershed, "iter_ws_blocks_stream", broken)
    else:
        from cluster_tools_tpu.ops import sweep

        orig = sweep.rle_decode_packed

        def broken(packed, n_runs, n):
            return apply(orig(packed, n_runs, n))

        monkeypatch.setattr(sweep, "rle_decode_packed", broken)
    return state


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


FAULT_CASES = ([(c, f) for c in CELLS for f in sorted(FRAGMENT_FAULTS)]
               + [("cremi_a_multicut.clean", f) for f in SEGMENT_FAULTS])


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_is_not_correct(monkeypatch, cell, fault):
    state = plant(monkeypatch, cell, fault)
    res = run_small(cell)
    assert state["hits"] > 0, "the fault was never reached"
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 2 ** 33 + 9])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(monkeypatch, cell, seed):
    _, cfg, _ = small(cell)
    ref = importlib.import_module("refs." + cfg["reference"]["name"])
    monkeypatch.setattr(ref, "compare", ref.compare)
    control.plant_control(ref)
    res = run_small(cell, seed)
    assert not res["correct"], res["checks"]
