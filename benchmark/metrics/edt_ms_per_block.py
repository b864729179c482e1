"""Device time of the Pallas EDT kernel (``ops/edt.py``, the only
``tpu_custom_call`` the program runs) per block processed in the traced
window, from the profiler trace."""


def read(run):
    red = run["trace"]
    blocks = run["blocks_per_chain"] * len(run["chains"])
    if not red or not red["custom_call_s"] or not blocks:
        return None
    return 1000.0 * red["custom_call_s"] / blocks
