"""Seconds per chain in which the chip idles while the program computes on
the host: the idle time of the traced window counted under a
``ctt.stage.host-*`` span (relabel maps and scans, RLE decode, the
multicut solve; ``benchmark/stage_reduce.py``, ``idle_by_stage_s``), over
the window's chains."""

import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "idle_s", os.path.join(_here, "idle_s.store.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

CLASS = "host"


def read(run):
    return _base.per_chain(run, CLASS)
