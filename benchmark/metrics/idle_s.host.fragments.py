"""Watershed (fragment-only) cells: the reader of ``idle_s.host``, under
the name that moves ``fragment_voxels_per_s`` (an end-to-end metric
holds one bound, and the two kinds of chain spread differently: PERF.md
section 2)."""

import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "idle_s.host", os.path.join(_here, "idle_s.host.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
