"""Wall time of the fused per-block pass (``workflows/fused_pipeline.py``,
task ``fused_segmentation``) per chain, from its status file."""


def read(run):
    walls = [c["status"]["fused_segmentation"]["wall_time"]
             for c in run["chains"] if "fused_segmentation" in c["status"]]
    return sum(walls) / len(walls) if walls else None
