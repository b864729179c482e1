"""Seconds per chain the runtime's executors (``core/runtime.py``) waited
on device programs (stage ``sync-execute``), summed over the chain's
tasks, from their status files."""


def read(run):
    per = []
    for c in run["chains"]:
        waits = [st["stages"]["sync-execute"] for st in c["status"].values()
                 if "sync-execute" in (st.get("stages") or {})]
        if waits:
            per.append(sum(waits))
    return sum(per) / len(per) if per else None
