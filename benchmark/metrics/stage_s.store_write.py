"""Seconds per chain spent writing to the store (``core/storage.py``,
stage ``store-write``), summed over the chain's tasks, from their status
files."""


def read(run):
    per = []
    for c in run["chains"]:
        writes = [st["stages"]["store-write"] for st in c["status"].values()
                  if "store-write" in (st.get("stages") or {})]
        if writes:
            per.append(sum(writes))
    return sum(per) / len(per) if per else None
