"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, from the profiler trace
(``benchmark/trace_reduce.py``), averaged over the chips used."""


def read(run):
    red = run["trace"]
    if not red or not red["n_events"] or run["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / run["window_s"])
