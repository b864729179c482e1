"""Wall time per chain of every task other than the fused device pass: the
graph and multicut tasks (``workflows/multicut.py``, ``workflows/graph.py``,
the native solver) and the final write, from their status files."""


def read(run):
    tails = [sum(st.get("wall_time", 0.0) for name, st in c["status"].items()
                 if name != "fused_segmentation")
             for c in run["chains"] if "fused_segmentation" in c["status"]]
    return sum(tails) / len(tails) if tails else None
