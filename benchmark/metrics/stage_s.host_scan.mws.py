"""Seconds per chain of the mutex-watershed host scans: the stage
``host-scan`` of the two passes' tasks (``mws_pass1``, ``mws_pass2``:
``workflows/mutex_watershed.py``, the union-find scan of each block's
sorted edge stream), from their status files.  Scans that overlap on
threads each count in full."""

TASKS = ("mws_pass1", "mws_pass2")


def summed(chain, field, name):
    """``status[field][name]`` summed over the chain's pass tasks; None
    where none of them has it (a program that does not record it)."""
    vals = [st[field][name] for task, st in chain["status"].items()
            if task.startswith(TASKS) and name in (st.get(field) or {})]
    return sum(vals) if vals else None


def read(run):
    per = [s for s in (summed(c, "stages", "host-scan")
                       for c in run["chains"]) if s is not None]
    return sum(per) / len(per) if per else None
