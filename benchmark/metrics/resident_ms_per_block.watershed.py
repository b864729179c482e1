"""Device time of the resident program's coarse watershed stage (scope
``watershed`` of ``workflows/fused_pipeline._resident_program``:
``ops/watershed._coarse_impl``) per block processed in the traced window,
from the scopes of the profiler trace's operations
(``benchmark/stage_reduce.py``, ``scope_s``)."""

import stage_reduce

SCOPES = ("watershed",)


def per_block(run, scopes):
    """Milliseconds of device time under ``scopes`` per block of the
    window's chains; None where the trace holds no such scope (a program
    without them)."""
    seconds = sum((stage_reduce.of_run(run)["scope_s"] or {}).get(s, 0.0)
                  for s in scopes)
    blocks = run["blocks_per_chain"] * len(run["chains"])
    if not seconds or not blocks:
        return None
    return 1000.0 * seconds / blocks


def read(run):
    return per_block(run, SCOPES)
