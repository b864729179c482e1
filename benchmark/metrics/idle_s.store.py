"""Seconds per chain in which the chip idles while the program waits on
the store: the idle time of the traced window counted under a
``ctt.stage.store-*`` or ``ctt.stage.prefetch-wait`` span
(``core/storage.py`` reads and writes; ``benchmark/stage_reduce.py``,
``idle_by_stage_s``), over the window's chains."""

import stage_reduce

CLASS = "store"


def per_chain(run, cls):
    """Idle seconds of class ``cls`` per chain; None where the trace holds
    no program stage span (a program that does not open them)."""
    idle = stage_reduce.of_run(run)["idle_by_stage_s"]
    if not idle or not run["chains"]:
        return None
    return idle[cls] / len(run["chains"])


def read(run):
    return per_chain(run, CLASS)
