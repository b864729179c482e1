"""Nanoseconds of mutex-watershed host scan per edge scanned: the seconds
of ``stage_s.host_scan.mws`` over the edges the scans consumed (the
count-only stage ``scan-edges`` of the pass tasks' status files,
``stage_counts``), over the window's chains.  None for a program that
does not count the edges."""

import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "stage_s.host_scan.mws", os.path.join(_here, "stage_s.host_scan.mws.py"))
_scan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scan)


def read(run):
    seconds = edges = 0
    for c in run["chains"]:
        s = _scan.summed(c, "stages", "host-scan")
        n = _scan.summed(c, "stage_counts", "scan-edges")
        if s is not None and n:
            seconds += s
            edges += n
    return 1e9 * seconds / edges if edges else None
