"""Watershed (fragment-only) cells: the reader of ``edt_ms_per_block``,
under the name that moves ``fragment_voxels_per_s`` (an end-to-end
metric holds one bound, and the two kinds of chain spread differently:
PERF.md section 2)."""

import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "edt_ms_per_block", os.path.join(_here, "edt_ms_per_block.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
