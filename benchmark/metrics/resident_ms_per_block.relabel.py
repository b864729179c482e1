"""Device time of the resident program's dense relabel per block processed
in the traced window (scope ``relabel`` of
``workflows/fused_pipeline._resident_program``: ``extent_valid_mask`` and
``dense_relabel``), from the scopes of the profiler trace's operations
(``benchmark/stage_reduce.py``, ``scope_s``)."""

import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "resident_ms_per_block", os.path.join(
        _here, "resident_ms_per_block.watershed.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

SCOPES = ("relabel",)


def read(run):
    return _base.per_block(run, SCOPES)
