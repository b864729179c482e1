"""BASELINE.json configs 2, 3 and 5: device vs reference-faithful CPU.

Complements bench.py (config 4, the flagship multicut chain; config 1 —
the single-block DT watershed — was measured in round 1).  Each config
runs the SAME workflow classes under ``target='tpu'`` and under
``target='local'`` (subprocess workers pinned to the CPU jax backend,
the reference's LocalTask model), reports voxels/sec for both, the ratio,
and a quality check against the generating ground truth:

* config 2 — ThresholdedComponentsWorkflow: distributed connected
  components with block stitching (offsets -> faces -> union-find).
  Oracle: partition-identical to scipy.ndimage.label.
* config 3 — MwsWorkflow: blockwise mutex watershed on 3D long-range
  affinities.  Quality: adapted Rand error vs the generating labels.
* config 5 — InferenceTask (3D U-Net affinity prediction, uint8
  requant) + MwsWorkflow on the predicted affinities.  The checkpoint is
  an untrained net (no trained weights ship with the repo), so the
  metric is pipeline throughput; segmentation quality is only asserted
  to be defined (the MWS consumes the real prediction output).

Writes one JSON per config: BENCH_config{2,3,5}.json at the repo root.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OFFSETS = [[-1, 0, 0], [0, -1, 0], [0, 0, -1],
           [-4, 0, 0], [0, -4, 0], [0, 0, -4]]


# env-overridable geometry for smoke runs on small hosts; every config
# records the shape it actually measured in its JSON
from bench import _env_shape  # noqa: E402  (same directory)
from cluster_tools_tpu.core.config import write_config  # noqa: E402


def _blob_volume(shape, seed=0):
    """Smoothed random field normalized to [0,1]: thresholding yields
    many multi-block blobs (O(volume) generation — per-blob meshgrids
    take minutes at benchmark scale)."""
    from scipy import ndimage

    rng = np.random.RandomState(seed)
    vol = ndimage.gaussian_filter(rng.rand(*shape).astype("float32"), 4.0)
    lo, hi = float(vol.min()), float(vol.max())
    return (vol - lo) / max(hi - lo, 1e-6)


def _voronoi_gt(shape, n_cells, seed=0):
    from scipy.spatial import cKDTree

    rng = np.random.RandomState(seed)
    pts = (rng.rand(n_cells, 3) * np.array(shape)).astype("float32")
    tree = cKDTree(pts)
    grids = np.meshgrid(*[np.arange(s, dtype="float32") for s in shape],
                        indexing="ij")
    _, idx = tree.query(np.stack([g.ravel() for g in grids], 1), k=1)
    return (idx + 1).reshape(shape).astype("uint64")


def _affs_from_gt(gt, offsets, hi=0.9, lo=0.05, noise=0.05, seed=0):
    rng = np.random.RandomState(seed)
    affs = np.full((len(offsets),) + gt.shape, lo, dtype="float32")
    for c, off in enumerate(offsets):
        sl_a, sl_b = [], []
        for o, s in zip(off, gt.shape):
            sl_a.append(slice(0, s - abs(o)) if o >= 0 else slice(-o, s))
            sl_b.append(slice(o, s) if o >= 0 else slice(0, s + o))
        same = gt[tuple(sl_a)] == gt[tuple(sl_b)]
        affs[c][tuple(sl_a)] = np.where(same, hi, lo)
    affs += (rng.rand(*affs.shape).astype("float32") - 0.5) * 2 * noise
    return np.clip(affs, 0.0, 1.0)


def _run_local_subprocess(fn_name, args, workdir):
    """Run one chain in a subprocess pinned to the CPU jax backend."""
    import pickle

    os.makedirs(workdir, exist_ok=True)
    out_path = os.path.join(workdir, "result.pkl")
    script = os.path.join(workdir, "chain.py")
    with open(script, "w") as f:
        f.write(f"""
import pickle, sys
sys.path.insert(0, {ROOT!r})
import bench_configs
res = bench_configs.{fn_name}(*{args!r}, target="local")
with open({out_path!r}, "wb") as fo:
    pickle.dump(res, fo)
""")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ([ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
        if p)
    rc = subprocess.call([sys.executable, script], env=env)
    assert rc == 0, f"{fn_name} local chain failed"
    with open(out_path, "rb") as f:
        return pickle.load(f)


def _workdir(name, target):
    base = os.path.join("/tmp/ctt_bench_cfg", f"{name}_{target}")
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    return base


# ---------------------------------------------------------------------------
# config 1: single-block distance-transform watershed (BASELINE.json
# config 1: "DT watershed on CREMI sample A boundary map, single block")
# ---------------------------------------------------------------------------

WS_SHAPE = _env_shape("BENCH_CFG_WS_SHAPE", (50, 512, 512))


def run_ws_chain(store, target="tpu"):
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

    workdir = _workdir("ws", target)
    cfg = ConfigDir(os.path.join(workdir, "configs"))
    # ONE block covering the volume: the single-block regime of config 1
    cfg.write_global_config({"block_shape": list(WS_SHAPE)})
    if target == "local":
        cfg.write_task_config("watershed", {"threshold": 0.4,
                                            "size_filter": 50,
                                            "impl": "host"})
    else:
        cfg.write_task_config("watershed", {"threshold": 0.4,
                                            "size_filter": 50})
    t0 = time.perf_counter()
    wf = WatershedWorkflow(
        input_path=store, input_key="bmap", output_path=store,
        output_key=f"ws_{target}", tmp_folder=workdir,
        config_dir=os.path.join(workdir, "configs"), max_jobs=1,
        target=target)
    assert ctt.build([wf], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store, "r") as f:
        seg = f[f"ws_{target}"][:]
    return elapsed, seg


def config1():
    from scipy.spatial import cKDTree

    from cluster_tools_tpu.core.storage import file_reader

    rng = np.random.RandomState(0)
    n_cells = max(int(np.prod(WS_SHAPE) / 70000), 8)
    pts = (rng.rand(n_cells, 3) * np.array(WS_SHAPE)).astype("float32")
    tree = cKDTree(pts)
    grids = np.meshgrid(*[np.arange(s, dtype="float32")
                          for s in WS_SHAPE], indexing="ij")
    d, _ = tree.query(np.stack([g.ravel() for g in grids], 1), k=2)
    bnd = np.exp(-0.5 * ((d[:, 1] - d[:, 0]) / 2.0) ** 2
                 ).reshape(WS_SHAPE).astype("float32")
    store = "/tmp/ctt_bench_cfg/ws.n5"
    shutil.rmtree(store, ignore_errors=True)
    with file_reader(store) as f:
        f.require_dataset("bmap", shape=WS_SHAPE, chunks=list(WS_SHAPE),
                          dtype="uint8")[:] = np.round(
                              bnd * 255).astype("uint8")

    run_ws_chain(store, "tpu")  # warm compiles
    dev_t, dev_seg = run_ws_chain(store, "tpu")
    cpu_t, cpu_seg = _run_local_subprocess(
        "run_ws_chain", (store,), "/tmp/ctt_bench_cfg/ws_local")

    # watershed fragments OVER-segment by design: quality here is that
    # both paths produce a dense fragment cover of comparable granularity
    # (VOI parity of the final segmentation is config 4's gate)
    n_dev = len(np.unique(dev_seg[dev_seg > 0]))
    n_cpu = len(np.unique(cpu_seg[cpu_seg > 0]))
    assert n_dev > n_cells / 2 and n_cpu > n_cells / 2, (n_dev, n_cpu)
    n = int(np.prod(WS_SHAPE))
    return {
        "config": 1,
        "workflow": "WatershedWorkflow (single-block DT watershed)",
        "volume_mvox": round(n / 1e6, 1), "shape": list(WS_SHAPE),
        "block_shape": list(WS_SHAPE),
        "device_vox_per_sec": round(n / dev_t, 1),
        "cpu_vox_per_sec": round(n / cpu_t, 1),
        "vs_baseline": round(cpu_t / dev_t, 3),
        "n_fragments": {"device": n_dev, "cpu": n_cpu},
        "quality": "dense fragment cover, comparable granularity "
                   "(VOI parity gated in config 4)",
    }


# ---------------------------------------------------------------------------
# config 2: connected components + stitching
# ---------------------------------------------------------------------------

CC_SHAPE = _env_shape("BENCH_CFG_CC_SHAPE", (64, 512, 512))
CC_BLOCK = [32, 256, 256]
#: ~500 components spanning blocks at this threshold of the smoothed field
CC_THRESHOLD = 0.6


def run_cc_chain(store, target="tpu"):
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.thresholded_components import (
        ThresholdedComponentsWorkflow)

    workdir = _workdir("cc", target)
    cfg = ConfigDir(os.path.join(workdir, "configs"))
    cfg.write_global_config({"block_shape": CC_BLOCK})
    t0 = time.perf_counter()
    wf = ThresholdedComponentsWorkflow(
        input_path=store, input_key="vol", output_path=store,
        output_key=f"cc_{target}", threshold=CC_THRESHOLD, tmp_folder=workdir,
        config_dir=os.path.join(workdir, "configs"),
        max_jobs=os.cpu_count() or 1, target=target)
    assert ctt.build([wf], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store, "r") as f:
        seg = f[f"cc_{target}"][:]
    return elapsed, seg


def config2():
    from scipy import ndimage

    from cluster_tools_tpu.core.storage import file_reader

    vol = _blob_volume(CC_SHAPE)
    store = "/tmp/ctt_bench_cfg/cc.n5"
    shutil.rmtree(store, ignore_errors=True)
    with file_reader(store) as f:
        f.require_dataset("vol", shape=vol.shape, chunks=CC_BLOCK,
                          dtype="float32")[:] = vol

    run_cc_chain(store, "tpu")  # warm compiles
    dev_t, dev_seg = run_cc_chain(store, "tpu")
    cpu_t, cpu_seg = _run_local_subprocess(
        "run_cc_chain", (store,), "/tmp/ctt_bench_cfg/cc_local")

    expected, _ = ndimage.label(vol > CC_THRESHOLD)
    for name, seg in (("device", dev_seg), ("cpu", cpu_seg)):
        pairs = np.unique(np.stack([seg.ravel(),
                                    expected.ravel().astype("uint64")]),
                          axis=1)
        assert len(np.unique(pairs[0])) == pairs.shape[1] \
            and len(np.unique(pairs[1])) == pairs.shape[1], \
            f"{name} partition differs from scipy.ndimage.label"
    n = int(np.prod(CC_SHAPE))
    return {
        "config": 2,
        "workflow": "ThresholdedComponentsWorkflow (CC + stitching)",
        "volume_mvox": round(n / 1e6, 1), "shape": list(CC_SHAPE),
        "block_shape": CC_BLOCK,
        "device_vox_per_sec": round(n / dev_t, 1),
        "cpu_vox_per_sec": round(n / cpu_t, 1),
        "vs_baseline": round(cpu_t / dev_t, 3),
        "quality": "partition-identical to scipy.ndimage.label (both)",
    }


# ---------------------------------------------------------------------------
# config 3: mutex watershed on long-range affinities
# ---------------------------------------------------------------------------

MWS_SHAPE = _env_shape("BENCH_CFG_MWS_SHAPE", (64, 512, 512))
MWS_BLOCK = [32, 256, 256]


def run_mws_chain(store, target="tpu"):
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.mutex_watershed import (
        TwoPassMwsWorkflow)

    workdir = _workdir("mws", target)
    cfg = ConfigDir(os.path.join(workdir, "configs"))
    cfg.write_global_config({"block_shape": MWS_BLOCK})
    t0 = time.perf_counter()
    # two-pass checkerboard: pass-2 blocks consume the serialized seeds of
    # pass-1 neighbors, then assignments stitch the grid — the
    # cross-block-consistent MWS (single-pass leaves per-block pieces)
    wf = TwoPassMwsWorkflow(
        input_path=store, input_key="affs", output_path=store,
        output_key=f"mws_{target}", offsets=OFFSETS, halo=[4, 16, 16],
        tmp_folder=workdir,
        config_dir=os.path.join(workdir, "configs"),
        max_jobs=os.cpu_count() or 1, target=target)
    assert ctt.build([wf], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store, "r") as f:
        seg = f[f"mws_{target}"][:]
    return elapsed, seg


def config3():
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.utils.validation import (ContingencyTable,
                                                    cremi_score_from_table)

    gt = _voronoi_gt(MWS_SHAPE, n_cells=240)
    affs = _affs_from_gt(gt, OFFSETS)
    store = "/tmp/ctt_bench_cfg/mws.n5"
    shutil.rmtree(store, ignore_errors=True)
    with file_reader(store) as f:
        f.require_dataset("affs", shape=affs.shape,
                          chunks=[1] + MWS_BLOCK, dtype="float32")[:] = affs

    run_mws_chain(store, "tpu")  # warm
    dev_t, dev_seg = run_mws_chain(store, "tpu")
    cpu_t, cpu_seg = _run_local_subprocess(
        "run_mws_chain", (store,), "/tmp/ctt_bench_cfg/mws_local")

    metrics = {}
    for name, seg in (("device", dev_seg), ("cpu", cpu_seg)):
        table = ContingencyTable.from_arrays_chunked(gt, seg)
        vs, vm, are, _ = cremi_score_from_table(table)
        metrics[name] = {"voi_split": round(vs, 4),
                         "voi_merge": round(vm, 4),
                         "rand_error": round(are, 4)}
        assert are < 0.1, f"{name} MWS lost parity: {are}"
    n = int(np.prod(MWS_SHAPE))
    return {
        "config": 3,
        "workflow": "TwoPassMwsWorkflow (checkerboard mutex watershed, "
                    f"{len(OFFSETS)} offsets)",
        "volume_mvox": round(n / 1e6, 1), "shape": list(MWS_SHAPE),
        "block_shape": MWS_BLOCK,
        "device_vox_per_sec": round(n / dev_t, 1),
        "cpu_vox_per_sec": round(n / cpu_t, 1),
        "vs_baseline": round(cpu_t / dev_t, 3),
        "device": metrics["device"], "cpu": metrics["cpu"],
    }


# ---------------------------------------------------------------------------
# config 5: U-Net affinity inference + mutex watershed
# ---------------------------------------------------------------------------

INF_SHAPE = _env_shape("BENCH_CFG_INF_SHAPE", (32, 256, 256))
INF_BLOCK = [16, 128, 128]


def _make_checkpoint(path):
    import jax

    from cluster_tools_tpu.models.checkpoint import save_checkpoint
    from cluster_tools_tpu.models.unet import create_unet

    model = create_unet(out_channels=len(OFFSETS), features=(8, 16))
    params = jax.tree_util.tree_map(
        np.asarray, model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 8, 16, 16, 1), "f4")))
    save_checkpoint(path, {"out_channels": len(OFFSETS),
                           "features": [8, 16]}, params)


def run_inference_chain(store, ckpt, target="tpu"):
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.inference import InferenceTask
    from cluster_tools_tpu.workflows.mutex_watershed import MwsWorkflow

    workdir = _workdir("inf", target)
    cfg = ConfigDir(os.path.join(workdir, "configs"))
    cfg.write_global_config({"block_shape": INF_BLOCK})
    t0 = time.perf_counter()
    inf = InferenceTask(
        input_path=store, input_key="raw", output_path=store,
        output_key={f"affs_{target}": [0, len(OFFSETS)]},
        checkpoint_path=ckpt, halo=[4, 16, 16], tmp_folder=workdir,
        config_dir=os.path.join(workdir, "configs"),
        max_jobs=os.cpu_count() or 1, target=target)
    mws = MwsWorkflow(
        input_path=store, input_key=f"affs_{target}", output_path=store,
        output_key=f"seg_{target}", offsets=OFFSETS, tmp_folder=workdir,
        config_dir=os.path.join(workdir, "configs"),
        max_jobs=os.cpu_count() or 1, target=target, dependency=inf)
    assert ctt.build([mws], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store, "r") as f:
        seg = f[f"seg_{target}"][:]
    return elapsed, seg


def config5():
    from cluster_tools_tpu.core.storage import file_reader

    rng = np.random.RandomState(0)
    raw = rng.rand(*INF_SHAPE).astype("float32")
    store = "/tmp/ctt_bench_cfg/inf.n5"
    shutil.rmtree(store, ignore_errors=True)
    with file_reader(store) as f:
        f.require_dataset("raw", shape=raw.shape, chunks=INF_BLOCK,
                          dtype="float32")[:] = raw
    ckpt = "/tmp/ctt_bench_cfg/ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    _make_checkpoint(ckpt)

    run_inference_chain(store, ckpt, "tpu")  # warm
    dev_t, dev_seg = run_inference_chain(store, ckpt, "tpu")
    cpu_t, cpu_seg = _run_local_subprocess(
        "run_inference_chain", (store, ckpt), "/tmp/ctt_bench_cfg/inf_local")
    assert dev_seg.shape == INF_SHAPE and cpu_seg.shape == INF_SHAPE
    n = int(np.prod(INF_SHAPE))
    return {
        "config": 5,
        "workflow": "InferenceTask (3D U-Net affinities, uint8 requant) "
                    "+ MwsWorkflow",
        "volume_mvox": round(n / 1e6, 1), "shape": list(INF_SHAPE),
        "block_shape": INF_BLOCK,
        "device_vox_per_sec": round(n / dev_t, 1),
        "cpu_vox_per_sec": round(n / cpu_t, 1),
        "vs_baseline": round(cpu_t / dev_t, 3),
        "quality": "untrained weights: throughput benchmark; MWS consumes "
                   "the real prediction output end-to-end",
    }


def main():
    sys.path.insert(0, ROOT)
    from cluster_tools_tpu.core.runtime import use_compile_cache

    use_compile_cache()
    os.makedirs("/tmp/ctt_bench_cfg", exist_ok=True)
    only = set(sys.argv[1:])
    todo = (("1", config1), ("2", config2), ("3", config3), ("5", config5))
    for name, fn in todo:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        res = fn()
        res["bench_seconds"] = round(time.perf_counter() - t0, 1)
        out = os.path.join(ROOT, f"BENCH_config{name}.json")
        write_config(out, res)
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
