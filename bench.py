"""Benchmark: full multicut segmentation workflow throughput at CREMI scale.

Config 4 of BASELINE.json ("MulticutSegmentationWorkflow: RAG + edge
features + hierarchical multicut") on a CREMI-sample-sized synthetic volume:
(125, 1250, 1250) ~= 195 Mvox (one CREMI sample is ~125x1250x1250) with the
reference's default block shape [50, 512, 512]
(reference: cluster_tasks.py:217).  The boundary map is stored uint8 — the
reference's own CNN-output convention (inference/inference.py:235 _to_uint8).

Two measurements:

* DEVICE: the complete framework chain (blockwise DT watershed -> RAG ->
  edge features -> costs -> multicut -> write) under ``target='tpu'``
  (inline executor owns the chip; blocks stream through fused jitted
  pipelines with async dispatch).  Runs the full volume twice and reports
  the steady-state second run (jit caches warm — the deployment regime;
  the first run pays one-time XLA compiles).
* CPU BASELINE: the SAME workflow classes under ``target='local'``
  (subprocess workers — the reference's LocalTask execution model) with
  ``impl='host'`` task configs that select the reference-faithful scipy C
  kernels (EDT / gaussian / maximum_filter / label / watershed_ift stand in
  one-for-one for the vigra calls) and numpy pair accumulation (the ndist
  C++ analog).  vigra/nifty themselves are not installable here, so this
  scipy path is the measured stand-in for the reference's CPU
  ``target='local'`` — same algorithm family, C implementations, same
  workflow semantics.  It is timed on a 2-block subvolume (50, 512, 1024)
  of the same instance and extrapolated per-voxel (the blockwise tasks are
  linear in blocks; the global reduce stages are a small, sublinear
  fraction) — a full-volume CPU run would take hours by itself.  The
  extrapolation assumes fixed worker parallelism: valid here because the
  subvolume holds at least cpu_count blocks on this single-core host; on a
  many-core machine the subvolume (or max_jobs) must be sized so the
  baseline saturates the same worker count as a full run would.

VARIANCE-PROOFING (r6): both measurements are MEDIANS over >= 3 trials
(``BENCH_TRIALS`` / ``BENCH_CPU_TRIALS`` env overrides).  The r5 headline
was a single trial whose ``sync-meta`` wait swung 5x between identical
runs — all one-time XLA compile mixed into execute waits.  The runtime
now times those separately (``sync-compile`` vs ``sync-execute``), every
trial's wall and per-stage breakdown is reported, and the CPU baseline is
pinned the same way: fixed worker count, JAX_PLATFORMS=cpu subprocess,
median over trials (its host-side throughput varies ~1.5x run-to-run on a
shared core — the median, not one draw, is the denominator).

Parity: BOTH chains must segment well in absolute terms — VOI, adapted
Rand error and CREMI score against the generating ground truth are
computed and reported for each (reference metric definitions:
utils/validation_utils.py:60-273).  The device chain is additionally run
on the CPU subvolume so the device<->CPU quality delta is measured on
identical data; the two paths use different (but same-family) watershed
implementations, so the comparison is VOI-level, not voxel-identical.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

# atomic artifact writes (tmp + os.replace): a watcher tailing BENCH_*
# JSON must never observe a truncated document (ctt-lint: atomic-write)
from cluster_tools_tpu.core.config import write_config

def _env_shape(name, default):
    val = os.environ.get(name)
    return tuple(int(x) for x in val.split(",")) if val else default


# env overrides exist for smoke-testing the harness on small hosts; the
# recorded BENCH numbers always use the defaults
SHAPE = _env_shape("BENCH_SHAPE", (125, 1250, 1250))   # ~195 Mvox: one CREMI sample
CPU_SHAPE = _env_shape("BENCH_CPU_SHAPE", (50, 512, 1024))  # 2 reference blocks
BLOCK = list(_env_shape("BENCH_BLOCK", (50, 512, 512)))  # reference default (cluster_tasks.py:217)
CELL_DENSITY = 70000             # voxels per cell (round-2 bench density)


def synthetic_instance(shape=SHAPE, n_cells=None, seed=0):
    """(ground_truth uint32, boundary float32): voronoi cells with smooth
    ridges, generated in z-slabs through a cKDTree (memory-bounded; the
    meshgrid-per-cell formulation would need dozens of full-volume
    temporaries at this scale)."""
    from scipy.spatial import cKDTree

    if n_cells is None:
        n_cells = max(int(np.prod(shape) / CELL_DENSITY), 8)
    rng = np.random.RandomState(seed)
    pts = (rng.rand(n_cells, 3) * np.array(shape)).astype("float32")
    tree = cKDTree(pts)
    lab = np.zeros(shape, "uint32")
    bnd = np.zeros(shape, "float32")
    slab = max(int(2e7 // (shape[1] * shape[2])), 1)
    yy, xx = np.meshgrid(np.arange(shape[1], dtype="float32"),
                         np.arange(shape[2], dtype="float32"),
                         indexing="ij")
    for z0 in range(0, shape[0], slab):
        z1 = min(z0 + slab, shape[0])
        q = np.empty(((z1 - z0) * shape[1] * shape[2], 3), "float32")
        for i, z in enumerate(range(z0, z1)):
            base = i * shape[1] * shape[2]
            q[base:base + shape[1] * shape[2], 0] = z
            q[base:base + shape[1] * shape[2], 1] = yy.ravel()
            q[base:base + shape[1] * shape[2], 2] = xx.ravel()
        d, idx = tree.query(q, k=2, workers=-1)
        lab[z0:z1] = (idx[:, 0] + 1).reshape(z1 - z0, shape[1], shape[2])
        bnd[z0:z1] = np.exp(
            -0.5 * ((d[:, 1] - d[:, 0]) / 2.0) ** 2
        ).reshape(z1 - z0, shape[1], shape[2]).astype("float32")
    return lab, bnd


def write_store(path, bnd):
    """Boundary map as uint8 (the reference's CNN-output requantization)."""
    from cluster_tools_tpu.core.storage import file_reader

    with file_reader(path) as f:
        ds = f.require_dataset("bmap", shape=bnd.shape, chunks=BLOCK,
                               dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")


def run_chain(store_path, shape, workdir, target, host_impl=False,
              max_jobs=None, fused_overrides=None):
    """One full MulticutSegmentationWorkflow run; returns (seconds, seg).
    ``fused_overrides`` merges into the fused_segmentation task config
    (``chip_smoke.py --chips 4`` selects the mesh-resident program)."""
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

    shutil.rmtree(workdir, ignore_errors=True)
    config_dir = os.path.join(workdir, "configs")
    cfg = ConfigDir(config_dir)
    # one retry absorbs a transient failure; a retry during the timed
    # run honestly counts against the measured wall
    cfg.write_global_config({"block_shape": BLOCK, "max_num_retries": 1})
    impl = {"impl": "host"} if host_impl else {}
    ws_params = {"threshold": 0.4, "size_filter": 50}
    cfg.write_task_config("watershed", {**ws_params, **impl})
    # resident device path: input volume uploaded once, per-block fused
    # program (coarse-basins watershed + RAG + stats), RLE label
    # downloads, in-RAM fragment staging for faces + final write
    # pair_cap: measured ~1.25M valid boundary PAIRS per [50,512,512]
    # block on this instance (the uint8 path compacts each pair once,
    # carrying both side samples); 2.1M adds ~65% margin (overflow falls
    # back to a worst-case-capacity redo, so the tight cap is safe)
    # coarse_factor 4 + 6 refine rounds: the r5 calibration puts the
    # basin solve at 0.19 s vs 0.82 s (2x) per block, and the measured
    # quality cost on a 100 Mvox instance is ~0.003 VOI (0.1867/0.1871
    # vs 0.1831/0.1846 split/merge) — far inside the 0.01 parity budget
    cfg.write_task_config("fused_segmentation",
                          {**ws_params, "pair_cap": 1 << 21,
                           "coarse_factor": 4, "refine_rounds": 6,
                           **(fused_overrides or {})})
    cfg.write_task_config("initial_sub_graphs", impl)
    cfg.write_task_config("block_edge_features", impl)
    if max_jobs is None:
        max_jobs = os.cpu_count() or 1
        if host_impl:
            # keep the per-voxel extrapolation honest: the baseline must
            # not run MORE workers per block than a full-volume run could
            n_blocks = int(np.prod([-(-s // b)
                                    for s, b in zip(shape, BLOCK)]))
            max_jobs = min(max_jobs, n_blocks)

    t0 = time.perf_counter()
    if target == "tpu":
        # fused device chain: ws + relabel + RAG + features in one device
        # program per block (workflows/fused_pipeline.py)
        mc = ctt.MulticutSegmentationWorkflow(
            input_path=store_path, input_key="bmap", ws_path=store_path,
            ws_key="ws", problem_path=os.path.join(workdir, "p.n5"),
            output_path=store_path, output_key="seg",
            tmp_folder=os.path.join(workdir, "tmp"),
            config_dir=config_dir, max_jobs=max_jobs, target=target,
            n_scales=1, fused=True)
    else:
        ws = WatershedWorkflow(
            input_path=store_path, input_key="bmap", output_path=store_path,
            output_key="ws", tmp_folder=os.path.join(workdir, "tmp"),
            config_dir=config_dir, max_jobs=max_jobs, target=target)
        mc = ctt.MulticutSegmentationWorkflow(
            input_path=store_path, input_key="bmap", ws_path=store_path,
            ws_key="ws", problem_path=os.path.join(workdir, "p.n5"),
            output_path=store_path, output_key="seg",
            tmp_folder=os.path.join(workdir, "tmp"),
            config_dir=config_dir, max_jobs=max_jobs, target=target,
            n_scales=1, dependency=ws)
    assert ctt.build([mc], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store_path, "r") as f:
        seg = f["seg"][:]
    return elapsed, seg


def run_cpu_chain_subprocess(store_path, shape, workdir):
    """CPU baseline in a subprocess pinned to the CPU jax backend."""
    import pickle

    script = os.path.join(workdir, "cpu_chain.py")
    os.makedirs(workdir, exist_ok=True)
    out_path = os.path.join(workdir, "cpu_result.pkl")
    with open(script, "w") as f:
        f.write(f"""
import os, sys, pickle
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import bench
t, seg = bench.run_chain({store_path!r}, {tuple(shape)!r},
                         {os.path.join(workdir, 'run')!r}, "local",
                         host_impl=True)
with open({out_path!r}, "wb") as fo:
    pickle.dump((t, seg), fo)
""")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.call([sys.executable, script], env=env)
    assert rc == 0, "cpu baseline chain failed"
    with open(out_path, "rb") as f:
        return pickle.load(f)


def task_profile(workdir):
    """Per-task wall times from the runtime's status JSONs."""
    import glob

    rows = []
    for sf in sorted(glob.glob(os.path.join(workdir, "tmp", "*.status"))):
        with open(sf) as f:
            st = json.load(f)
        rows.append((st.get("wall_time", 0.0), st["task"], st.get("n_blocks"),
                     st.get("stages") or {}, st.get("bytes_moved") or {}))
    return sorted(rows, key=lambda r: -r[0])


def metrics(seg, gt):
    """All metrics from ONE streamed contingency table: three separate
    full-volume table builds held multi-GB uint64 temporaries (the r3
    bench peaked at 15 GB RSS largely here)."""
    from cluster_tools_tpu.utils.validation import (ContingencyTable,
                                                    cremi_score_from_table)

    table = ContingencyTable.from_arrays_chunked(gt, seg)
    vs, vm, are, cs = cremi_score_from_table(table)
    return {"voi_split": round(float(vs), 4), "voi_merge": round(float(vm), 4),
            "rand_error": round(float(are), 4), "cremi": round(float(cs), 4)}


def _profile_rows(profile):
    return [{"task": task, "wall_s": round(wall, 2),
             "n_blocks": n_blocks, "stages": stages, "bytes_moved": mb}
            for wall, task, n_blocks, stages, mb in profile]


# ---------------------------------------------------------------------------
# `mesh` config: per-device-count scaling of the MESH-RESIDENT flagship
# (one shard_map program for the whole volume, workflows/fused_pipeline
# _process_mesh) vs the per-block streamed path at equal volume.  Each
# device count runs in its OWN subprocess so XLA_FLAGS
# --xla_force_host_platform_device_count binds before jax imports — the
# standard virtual-mesh technique; on this CPU-only container all virtual
# devices share one core, so the scaling series measures the DISPATCH
# model (program count, sync-execute waits, compile cost), not chip
# speedup.  Invoke with `python bench.py mesh` (or BENCH_MESH=1); writes
# BENCH_mesh.json.
# ---------------------------------------------------------------------------

MESH_SHAPE = _env_shape("BENCH_MESH_SHAPE", (48, 128, 128))
MESH_BLOCK = list(_env_shape("BENCH_MESH_BLOCK", (16, 64, 64)))
MESH_DEVICES = tuple(int(d) for d in os.environ.get(
    "BENCH_MESH_DEVICES", "1,2,4,8").split(","))

# VOI-parity bars of the mesh series (reconciled r8; BASELINE.md
# "Mesh-resident mode"): the deployed configuration — the FULL mesh —
# carries the strict 0.01 gate; partial-mesh rows are the seam-count
# ablation (fewer slab seams than the block grid; devices=1 has ZERO
# seams) and carry a sanity bound only
VOI_GATE_FULL_MESH = 0.01
VOI_GATE_PARTIAL_MESH = 0.05


def run_mesh_chain(store_path, workdir, mesh_resident, n_devices,
                   extra_global=None):
    """One flagship run (optionally mesh-resident) returning
    (elapsed, seg, fused-task status dict).  ``n_devices`` is asserted,
    not set — the device count binds at backend init via XLA_FLAGS, which
    is why _run_mesh_subprocess launches one process per count.
    ``extra_global`` merges extra keys into the global config (the trace
    config uses it to arm ``telemetry_enabled``)."""
    import jax

    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader

    assert len(jax.devices()) == int(n_devices), \
        (len(jax.devices()), n_devices)
    shutil.rmtree(workdir, ignore_errors=True)
    config_dir = os.path.join(workdir, "configs")
    cfg = ConfigDir(config_dir)
    cfg.write_global_config({"block_shape": MESH_BLOCK,
                             "max_num_retries": 0,
                             **(extra_global or {})})
    cfg.write_task_config("fused_segmentation", {
        "threshold": 0.4, "size_filter": 50, "halo": [2, 8, 8],
        "mesh_resident": bool(mesh_resident), "mesh_shards": 0})
    t0 = time.perf_counter()
    mc = ctt.MulticutSegmentationWorkflow(
        input_path=store_path, input_key="bmap", ws_path=store_path,
        ws_key=f"ws", problem_path=os.path.join(workdir, "p.n5"),
        output_path=store_path, output_key="seg",
        tmp_folder=os.path.join(workdir, "tmp"), config_dir=config_dir,
        max_jobs=1, target="tpu", n_scales=1, fused=True)
    assert ctt.build([mc], raise_on_failure=True)
    elapsed = time.perf_counter() - t0
    with file_reader(store_path, "r") as f:
        seg = f["seg"][:]
    with open(os.path.join(workdir, "tmp",
                           "fused_segmentation.status")) as f:
        status = json.load(f)
    status["platform"] = jax.devices()[0].platform
    return elapsed, seg, status


def _subprocess_env(extra_env=None, strip_exec_cache=True):
    """Env for bench subprocesses: by default both persistent compile
    caches stripped so compile-measuring configs stay cold.  ONE home for
    this logic — the mesh and warm harnesses must not drift apart."""
    env = dict(os.environ)
    if strip_exec_cache:
        env.pop("CTT_EXEC_CACHE_DIR", None)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    return env


def _run_mesh_subprocess(store_path, workdir, mesh_resident, n_devices,
                         extra_env=None):
    """run_mesh_chain in a subprocess with an n_devices virtual mesh.

    The persistent executable cache env is STRIPPED by default: the mesh
    series measures the dispatch model INCLUDING the one-time compile,
    and an inherited warm disk tier would silently zero `sync-compile`.
    The warm bench opts back in through ``extra_env``.
    """
    import pickle

    os.makedirs(workdir, exist_ok=True)
    out_path = os.path.join(workdir, "result.pkl")
    script = os.path.join(workdir, "chain.py")
    with open(script, "w") as f:
        f.write(f"""
import os, pickle, sys
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
flags = " ".join(t for t in flags.split()
                 if "xla_force_host_platform_device_count" not in t)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count={n_devices}").strip()
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import bench
t, seg, status = bench.run_mesh_chain(
    {store_path!r}, {os.path.join(workdir, 'run')!r},
    {bool(mesh_resident)!r}, {n_devices!r})
with open({out_path!r}, "wb") as fo:
    pickle.dump((t, seg, status), fo)
""")
    rc = subprocess.call([sys.executable, script],
                         env=_subprocess_env(extra_env))
    assert rc == 0, f"mesh chain failed (devices={n_devices})"
    with open(out_path, "rb") as f:
        return pickle.load(f)


def main_mesh():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    base = "/tmp/ctt_bench_mesh"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)

    lab, bnd = synthetic_instance(MESH_SHAPE, seed=0)
    store = os.path.join(base, "vol.n5")
    from cluster_tools_tpu.core.storage import file_reader

    with file_reader(store) as f:
        ds = f.require_dataset("bmap", shape=bnd.shape, chunks=MESH_BLOCK,
                               dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")
    n_vox = int(np.prod(MESH_SHAPE))

    def seg_metrics(seg):
        from cluster_tools_tpu.utils.validation import (
            ContingencyTable, cremi_score_from_table)

        t = ContingencyTable.from_arrays_chunked(lab, seg)
        vs, vm, are, _ = cremi_score_from_table(t)
        return {"voi_split": round(float(vs), 4),
                "voi_merge": round(float(vm), 4),
                "rand_error": round(float(are), 4)}

    def fused_row(status):
        return {
            "fused_wall_s": round(status.get("wall_time", 0.0), 2),
            "stages": {k: round(v, 2) for k, v in
                       (status.get("stages") or {}).items()},
            "stage_counts": status.get("stage_counts") or {},
        }

    # per-block reference at the same volume (wait-count comparison)
    t_b, seg_b, st_b = _run_mesh_subprocess(
        store, os.path.join(base, "blockwise"), False, max(MESH_DEVICES))
    block_entry = {"mode": "per-block", "devices": max(MESH_DEVICES),
                   "wall_s": round(t_b, 2),
                   "vox_per_sec": round(n_vox / t_b, 1),
                   **fused_row(st_b), **seg_metrics(seg_b)}
    print(json.dumps(block_entry), file=sys.stderr, flush=True)

    rows = []
    voi_b = block_entry["voi_split"] + block_entry["voi_merge"]
    for d in MESH_DEVICES:
        t_m, seg_m, st_m = _run_mesh_subprocess(
            store, os.path.join(base, f"mesh_d{d}"), True, d)
        row = {"mode": "mesh-resident", "devices": d,
               "wall_s": round(t_m, 2),
               "vox_per_sec": round(n_vox / t_m, 1),
               **fused_row(st_m), **seg_metrics(seg_m)}
        row["voi_delta_vs_blockwise"] = round(
            abs(row["voi_split"] + row["voi_merge"] - voi_b), 4)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    # gates: quality parity with the blockwise path, and the dispatch
    # model — ONE steady-state wait per volume vs one per block.  The
    # strict <= 0.01 VOI parity is gated on the FULL mesh (the deployed
    # configuration: mesh_shards 0 = all devices; tests pin it on a
    # fixed >= 4-device geometry too).  Partial-mesh rows are the
    # seam-count ablation — fewer devices mean fewer slab seams than
    # the block grid (devices=1: ZERO seams), so on a smoke-sized
    # instance (~10 cells) their partitions legitimately diverge by
    # more than the parity budget; they carry a sanity bound only.
    # Each row RECORDS the bound it was gated against (``voi_gate``) so
    # the committed artifact is self-describing — a 0.03 delta on a
    # 1-device ablation row is inside ITS bar, not a missed 0.01 gate
    full_mesh = max(rows, key=lambda r: r["devices"])
    for row in rows:
        row["voi_gate"] = VOI_GATE_FULL_MESH if row is full_mesh \
            else VOI_GATE_PARTIAL_MESH
        assert row["voi_delta_vs_blockwise"] <= row["voi_gate"], row
        assert row["stage_counts"].get("sync-execute") == 1, row
    assert full_mesh["devices"] >= 4, full_mesh
    assert block_entry["stage_counts"].get("sync-execute", 0) > 1, \
        block_entry

    out = {
        "metric": "mesh_resident_flagship_scaling",
        "platform": st_b["platform"],
        "shape": list(MESH_SHAPE),
        "block_shape": MESH_BLOCK,
        "volume_mvox": round(n_vox / 1e6, 2),
        "note": ("CPU-emulated mesh (--xla_force_host_platform_device_"
                 "count): all virtual devices share one core, so the "
                 "series measures the dispatch model — one compiled "
                 "program and ONE sync-execute wait per volume vs one "
                 "per block — not chip speedup; see BASELINE.md "
                 "'Mesh-resident mode'"),
        "gates": {
            "voi_delta_full_mesh": VOI_GATE_FULL_MESH,
            "voi_delta_partial_mesh": VOI_GATE_PARTIAL_MESH,
            "note": ("strict VOI parity is gated on the FULL mesh (the "
                     "deployed configuration); partial-mesh rows are the "
                     "seam-count ablation — fewer z-slab seams than the "
                     "block grid (devices=1: zero seams) legitimately "
                     "shift the partition on a smoke-sized instance, so "
                     "they carry a sanity bound only (each row records "
                     "its own voi_gate)"),
        },
        "per_block": block_entry,
        "mesh": rows,
    }
    from cluster_tools_tpu.core import telemetry
    out["memory"] = telemetry.memory_rollup()
    out["peak_rss_gb"] = round(telemetry.host_peak_rss_gb(), 2)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_mesh.json")
    write_config(path, out)
    print(json.dumps({"metric": out["metric"],
                      "platform": out["platform"],
                      "shape": out["shape"],
                      "per_block_wall_s": block_entry["wall_s"],
                      "mesh_walls_s": [r["wall_s"] for r in rows],
                      "mesh_devices": [r["devices"] for r in rows],
                      "sync_execute_waits": {
                          "per_block":
                              block_entry["stage_counts"].get(
                                  "sync-execute"),
                          "mesh": [r["stage_counts"].get("sync-execute")
                                   for r in rows]},
                      "detail": os.path.basename(path)}))


# ---------------------------------------------------------------------------
# `warm` config: compile amortization through the PERSISTENT executable
# cache (core.runtime compile_cached disk tier).  Three measurements, each
# in its own fresh process so nothing is warm except the DISK:
#
#   1. cold  — mesh-resident flagship, empty cache dir: pays the full XLA
#              build (sync-compile) and populates the disk tier;
#   2. warm  — the SAME run again in a fresh process: sync-compile is a
#              deserialize, the wall collapses to execute + host tail;
#   3. tenants — the resident multi-tenant server (core/server.py):
#              N tenants issue small ROI requests; the FIRST request pays
#              the compile (cold request latency), later requests are
#              pure cache hits (warm latency) — run twice, so the second
#              harness process also shows the first request warm via disk.
#
# On this 1-core emulated mesh the numbers measure COMPILE AMORTIZATION
# (the dispatch/caching model), not chip speed — see BASELINE.md
# "Warm-path semantics".  Invoke with `python bench.py warm`; writes
# BENCH_warm.json.
# ---------------------------------------------------------------------------

WARM_ROI_SHAPE = _env_shape("BENCH_WARM_ROI", (16, 64, 64))
WARM_TENANTS = max(int(os.environ.get("BENCH_WARM_TENANTS", "2")), 2)
# >= 2: wave 0 is the cold measurement, later waves are the warm ones
WARM_WAVES = max(int(os.environ.get("BENCH_WARM_WAVES", "3")), 2)


def _run_tenant_harness(workdir, cache_dir, n_tenants, n_waves):
    """The multi-tenant server harness in a fresh subprocess: returns
    {"waves": [[{tenant, latency_s, queue_wait_s, exec_cache}, ...], ...],
    "exec_cache_total": ...}.  Requests are issued in WAVES (one request
    per tenant, wait for all, repeat) so per-request latency is
    queue-comparable across waves."""
    os.makedirs(workdir, exist_ok=True)
    out_path = os.path.join(workdir, "result.json")
    script = os.path.join(workdir, "harness.py")
    with open(script, "w") as f:
        f.write(f"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import jax
import numpy as np
import bench
from cluster_tools_tpu.core import runtime as rt
from cluster_tools_tpu.core.server import (FusedROIPipeline,
                                           ResidentSegmentationServer)

shape = {tuple(WARM_ROI_SHAPE)!r}
_, bnd = bench.synthetic_instance(shape, seed=7)
vol = np.round(bnd * 255).astype("uint8")
pipe = FusedROIPipeline(shape, block_shape=tuple(s // 2 for s in shape),
                        halo=(2, 8, 8))
waves = []
with ResidentSegmentationServer({os.path.join(workdir, 'srv')!r},
                                pipe) as srv:
    for wave in range({n_waves!r}):
        handles = [(f"tenant{{i}}", srv.submit(f"tenant{{i}}", vol))
                   for i in range({n_tenants!r})]
        rows = []
        for tenant, h in handles:
            h.result(600)
            st = json.load(open(h.status_path))
            rows.append({{"tenant": tenant,
                          "latency_s": st["wall_time"],
                          "queue_wait_s": st["queue_wait_s"],
                          "exec_cache": st["exec_cache"]}})
        waves.append(rows)
with open({out_path!r}, "w") as fo:
    json.dump({{"waves": waves,
               "platform": jax.devices()[0].platform,
               "exec_cache_total": rt.exec_cache_snapshot()}}, fo)
""")
    rc = subprocess.call([sys.executable, script], env=_subprocess_env(
        {"CTT_EXEC_CACHE_DIR": cache_dir}))
    assert rc == 0, "tenant harness failed"
    with open(out_path) as f:
        return json.load(f)


def main_warm():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    base = "/tmp/ctt_bench_warm"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    cache_dir = os.path.join(base, "exec_cache")

    lab, bnd = synthetic_instance(MESH_SHAPE, seed=0)
    store = os.path.join(base, "vol.n5")
    from cluster_tools_tpu.core.storage import file_reader

    with file_reader(store) as f:
        ds = f.require_dataset("bmap", shape=bnd.shape, chunks=MESH_BLOCK,
                               dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")
    n_vox = int(np.prod(MESH_SHAPE))
    n_dev = max(MESH_DEVICES)
    cache_env = {"CTT_EXEC_CACHE_DIR": cache_dir}

    def flagship_row(tag, t, status):
        st = status.get("stages") or {}
        return {"run": tag, "wall_s": round(t, 2),
                "vox_per_sec": round(n_vox / t, 1),
                "fused_wall_s": round(status.get("wall_time", 0.0), 2),
                "sync_compile_s": round(st.get("sync-compile", 0.0), 2),
                "sync_execute_s": round(st.get("sync-execute", 0.0), 2),
                "exec_cache": status.get("exec_cache") or {}}

    # 1+2: cold then warm flagship, each in a FRESH process; only the
    # disk cache dir is shared
    t_c, seg_c, st_c = _run_mesh_subprocess(
        store, os.path.join(base, "cold"), True, n_dev,
        extra_env=cache_env)
    cold = flagship_row("cold", t_c, st_c)
    print(json.dumps(cold), file=sys.stderr, flush=True)
    t_w, seg_w, st_w = _run_mesh_subprocess(
        store, os.path.join(base, "warm"), True, n_dev,
        extra_env=cache_env)
    warm = flagship_row("warm", t_w, st_w)
    print(json.dumps(warm), file=sys.stderr, flush=True)

    # identical results cold vs warm: the deserialized executable IS the
    # compiled one
    np.testing.assert_array_equal(seg_c, seg_w)

    # 3: multi-tenant server harness — cold-cache process, then a second
    # process against the now-populated disk tier
    tenants_cold = _run_tenant_harness(
        os.path.join(base, "tenants_cold"), cache_dir,
        WARM_TENANTS, WARM_WAVES)
    tenants_warm = _run_tenant_harness(
        os.path.join(base, "tenants_warm"), cache_dir,
        WARM_TENANTS, WARM_WAVES)

    def wave_latencies(h):
        return [[round(r["latency_s"], 2) for r in wave]
                for wave in h["waves"]]

    cold_req = max(r["latency_s"] for r in tenants_cold["waves"][0])
    warm_reqs = [r["latency_s"] for wave in tenants_cold["waves"][1:]
                 for r in wave]
    warm_req = float(sorted(warm_reqs)[len(warm_reqs) // 2])
    disk_first_req = max(r["latency_s"]
                         for r in tenants_warm["waves"][0])

    # ---- gates (the ISSUE acceptance) --------------------------------
    assert warm["sync_compile_s"] <= 0.10 * cold["sync_compile_s"], \
        (warm["sync_compile_s"], cold["sync_compile_s"])
    assert cold["wall_s"] / warm["wall_s"] >= 3.0, (cold, warm)
    assert warm["exec_cache"].get("disk_hits", 0) >= 1, warm
    assert warm["exec_cache"].get("compiles", 0) == 0, warm
    assert cold["exec_cache"].get("compiles", 0) >= 1, cold
    served = {r["tenant"] for wave in tenants_cold["waves"] for r in wave}
    assert len(served) >= 2, served
    assert warm_req < 0.5 * cold_req, (warm_req, cold_req)
    # the populated disk tier also makes a fresh server process warm:
    # its FIRST request deserializes instead of compiling
    assert disk_first_req < 0.5 * cold_req, (disk_first_req, cold_req)

    out = {
        "metric": "warm_path_compile_amortization",
        "platform": {"flagship": st_c["platform"],
                     "tenants": tenants_cold["platform"]},
        "shape": list(MESH_SHAPE),
        "block_shape": MESH_BLOCK,
        "volume_mvox": round(n_vox / 1e6, 2),
        "devices": n_dev,
        "note": ("persistent executable cache (compile_cached disk "
                 "tier): cold vs warm are IDENTICAL runs in fresh "
                 "processes sharing only the cache dir.  On this 1-core "
                 "emulated mesh the ratio measures compile "
                 "amortization, not chip speed — see BASELINE.md "
                 "'Warm-path semantics'"),
        "flagship": {
            "cold": cold, "warm": warm,
            "warm_speedup": round(t_c / t_w, 2),
            "sync_compile_ratio": round(
                warm["sync_compile_s"] / max(cold["sync_compile_s"],
                                             1e-9), 4),
            "bitwise_identical": True,
        },
        "tenants": {
            "roi_shape": list(WARM_ROI_SHAPE),
            "n_tenants": WARM_TENANTS,
            "waves_per_process": WARM_WAVES,
            "cold_process": {
                "wave_latencies_s": wave_latencies(tenants_cold),
                "cold_request_s": round(cold_req, 2),
                "warm_request_median_s": round(warm_req, 2),
                "exec_cache_total": tenants_cold["exec_cache_total"],
            },
            "warm_process": {
                "wave_latencies_s": wave_latencies(tenants_warm),
                "first_request_s": round(disk_first_req, 2),
                "exec_cache_total": tenants_warm["exec_cache_total"],
            },
        },
        "gates": {
            "warm_sync_compile_max_frac": 0.10,
            "warm_wall_min_speedup": 3.0,
            "warm_request_max_frac_of_cold": 0.5,
            "min_tenants": 2,
        },
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_warm.json")
    write_config(path, out)
    print(json.dumps({
        "metric": out["metric"],
        "platform": out["platform"],
        "cold_wall_s": cold["wall_s"], "warm_wall_s": warm["wall_s"],
        "warm_speedup": out["flagship"]["warm_speedup"],
        "sync_compile_s": {"cold": cold["sync_compile_s"],
                           "warm": warm["sync_compile_s"]},
        "tenant_request_s": {"cold": round(cold_req, 2),
                             "warm": round(warm_req, 2),
                             "fresh_process_warm_disk":
                                 round(disk_first_req, 2)},
        "detail": os.path.basename(path)}))


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    n_trials = max(int(os.environ.get("BENCH_TRIALS", "3")), 1)
    n_cpu_trials = max(int(os.environ.get("BENCH_CPU_TRIALS", "3")), 1)

    base = "/tmp/ctt_bench"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base, exist_ok=True)

    t0 = time.perf_counter()
    lab, bnd = synthetic_instance()
    print(f"generated {np.prod(SHAPE)/1e6:.0f} Mvox instance in "
          f"{time.perf_counter()-t0:.0f}s", file=sys.stderr, flush=True)

    full_store = os.path.join(base, "full.n5")
    cpu_store = os.path.join(base, "cpu.n5")
    write_store(full_store, bnd)
    cpu_crop = tuple(slice(0, s) for s in CPU_SHAPE)
    write_store(cpu_store, bnd[cpu_crop])
    gt_path = os.path.join(base, "gt.npy")
    np.save(gt_path, lab)
    lab_cpu = lab[cpu_crop].copy()  # copy: a view would pin the full volume past `del lab`
    del lab, bnd  # chains stream from the store; keep RSS bounded

    n_voxels = int(np.prod(SHAPE))
    n_cpu_voxels = int(np.prod(CPU_SHAPE))

    # device: subvolume first (pays most compiles + gives the same-data
    # quality comparison), one full warm run (remaining one-time compiles,
    # excluded from the timing like any deployment's warm-up), then
    # n_trials timed steady-state runs — the MEDIAN is the headline
    _, dev_seg_sub = run_chain(cpu_store, CPU_SHAPE,
                               os.path.join(base, "dev_sub"), "tpu")
    run_chain(full_store, SHAPE, os.path.join(base, "dev_warm"), "tpu")
    dev_trials = []
    dev_seg = None
    for ti in range(n_trials):
        workdir = os.path.join(base, f"dev_t{ti}")
        dev_t, dev_seg = run_chain(full_store, SHAPE, workdir, "tpu")
        profile = task_profile(workdir)
        dev_trials.append({"wall_s": round(dev_t, 2),
                           "vox_per_sec": round(n_voxels / dev_t, 1),
                           "tasks": _profile_rows(profile)})
        print(f"device trial {ti}: {dev_t:.1f}s "
              f"({n_voxels/dev_t/1e6:.2f} Mvox/s)",
              file=sys.stderr, flush=True)
        for wall, task, n_blocks, stages, dbf, mb in profile[:8]:
            stage_txt = " ".join(f"{k}={v:.1f}" for k, v in stages.items())
            dbf_txt = f" dev_frac={dbf:.2f}" if dbf is not None else ""
            print(f"  device task {task:40s} wall={wall:7.2f}s "
                  f"n_blocks={n_blocks}{dbf_txt} {stage_txt}",
                  file=sys.stderr, flush=True)
        if ti < n_trials - 1:
            shutil.rmtree(workdir, ignore_errors=True)  # bound disk
    # headline and breakdown must come from the SAME run: take the middle
    # trial by wall (for even trial counts np.median would interpolate a
    # wall no trial actually had, irreconcilable with its stage table)
    dev_walls = [t["wall_s"] for t in dev_trials]
    median_trial = dev_trials[int(np.argsort(dev_walls)[len(dev_walls) // 2])]
    dev_t = float(median_trial["wall_s"])

    # pinned CPU baseline: same fixed worker count and JAX_PLATFORMS=cpu
    # subprocess every trial; the median absorbs the ~1.5x host-side
    # throughput swings of a shared core
    cpu_walls = []
    cpu_seg = None
    for ti in range(n_cpu_trials):
        cpu_t_i, cpu_seg = run_cpu_chain_subprocess(
            cpu_store, CPU_SHAPE, os.path.join(base, f"cpu_t{ti}"))
        cpu_walls.append(round(cpu_t_i, 2))
        print(f"cpu trial {ti}: {cpu_t_i:.1f}s", file=sys.stderr, flush=True)
        shutil.rmtree(os.path.join(base, f"cpu_t{ti}"), ignore_errors=True)
    cpu_t = float(sorted(cpu_walls)[len(cpu_walls) // 2])

    gt = np.load(gt_path)
    dev_m = metrics(dev_seg, gt)
    del gt, dev_seg
    cpu_m = metrics(cpu_seg, lab_cpu)
    dev_sub_m = metrics(dev_seg_sub, lab_cpu)
    voi_delta = round(abs((dev_sub_m["voi_split"] + dev_sub_m["voi_merge"])
                          - (cpu_m["voi_split"] + cpu_m["voi_merge"])), 4)

    from cluster_tools_tpu.core import telemetry

    peak_rss_gb = telemetry.host_peak_rss_gb()
    print(f"device full (median of {n_trials}): {dev_t:.1f}s {dev_m}; cpu "
          f"baseline ({n_cpu_voxels/1e6:.0f} Mvox subvolume, median of "
          f"{n_cpu_trials}): {cpu_t:.1f}s {cpu_m}; device-on-subvolume "
          f"{dev_sub_m}; peak RSS {peak_rss_gb:.1f} GB",
          file=sys.stderr, flush=True)

    # quality gates: both chains must segment well in absolute terms, and
    # the algorithm-family difference must stay inside the VOI parity
    # budget on identical data (acceptance: same-data delta <= 0.01).
    # Smoke-sized env-override volumes hold too few cells for the delta
    # to be meaningful — only the absolute gates apply there
    smoke = any(os.environ.get(v) for v in
                ("BENCH_SHAPE", "BENCH_CPU_SHAPE", "BENCH_BLOCK"))
    assert dev_m["rand_error"] < 0.1, f"device lost parity: {dev_m}"
    assert cpu_m["rand_error"] < 0.1, f"cpu baseline lost parity: {cpu_m}"
    assert voi_delta <= (0.25 if smoke else 0.01), \
        f"device<->cpu VOI delta too large: {voi_delta}"
    # memory stays bounded: streamed block windows + bounded writer-pool
    # backpressure, not volume-sized device/host buffers
    assert peak_rss_gb < 7.0, f"peak RSS {peak_rss_gb:.1f} GB unbounded?"

    value = n_voxels / dev_t
    baseline = n_cpu_voxels / cpu_t
    # the FULL report (every trial's wall + per-stage/per-task breakdown,
    # bytes moved) goes to a file; stdout carries one COMPACT JSON line —
    # the harness that records bench output keeps only the last ~2000
    # characters, and the r5 line outgrew that and became unparseable
    full = {
        "metric": "multicut_workflow_throughput",
        "value": round(value, 1),
        "unit": "voxels/sec",
        "vs_baseline": round(value / baseline, 3),
        "volume_mvox": round(n_voxels / 1e6, 1),
        # the measured geometry, explicit: env-override smoke runs on
        # small hosts must be distinguishable from the default instance
        "shape": list(SHAPE),
        "cpu_shape": list(CPU_SHAPE),
        "smoke": smoke,
        "block_shape": BLOCK,
        "n_trials": n_trials,
        "trial_walls_s": dev_walls,
        "baseline_vox_per_sec": round(baseline, 1),
        "baseline_trial_walls_s": cpu_walls,
        "baseline_note": ("reference-faithful scipy chain, target='local', "
                          f"{n_cpu_voxels/1e6:.0f} Mvox subvolume, "
                          "per-voxel extrapolated, median of "
                          f"{n_cpu_trials} pinned trials (fixed worker "
                          "count, JAX_PLATFORMS=cpu)"),
        "device": dev_m, "cpu": cpu_m, "device_on_cpu_subvolume": dev_sub_m,
        "voi_delta_same_data": voi_delta,
        "peak_rss_gb": round(peak_rss_gb, 2),
        # per-task utilization of the MEDIAN trial (one-time XLA builds
        # split out as sync-compile vs steady-state sync-execute) + every
        # trial's full breakdown: progress claims must survive the
        # variance the r5 single-trial headline hid
        "tasks": median_trial["tasks"],
        "trials": dev_trials,
    }
    detail_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r06_full.json")
    write_config(detail_path, full)
    print(f"full per-trial report: {detail_path}", file=sys.stderr,
          flush=True)

    by_task = {r["task"]: r for r in median_trial["tasks"]}
    fused = by_task.get("fused_segmentation", {})
    wmc = by_task.get("write_multicut", {})
    print(json.dumps({
        "metric": "multicut_workflow_throughput",
        "value": round(value, 1),
        "unit": "voxels/sec",
        "vs_baseline": round(value / baseline, 3),
        "volume_mvox": round(n_voxels / 1e6, 1),
        "shape": list(SHAPE),
        "smoke": smoke,
        "n_trials": n_trials,
        "trial_walls_s": dev_walls,
        "baseline_vox_per_sec": round(baseline, 1),
        "baseline_trial_walls_s": cpu_walls,
        "device": dev_m, "cpu": cpu_m,
        "voi_delta_same_data": voi_delta,
        "peak_rss_gb": round(peak_rss_gb, 2),
        "fused_wall_s": fused.get("wall_s"),
        "fused_stages": {k: round(v, 1) for k, v in
                         (fused.get("stages") or {}).items()},
        "write_multicut_wall_s": wmc.get("wall_s"),
        "write_multicut_stages": {k: round(v, 1) for k, v in
                                  (wmc.get("stages") or {}).items()},
        "detail": os.path.basename(detail_path),
    }))


# ---------------------------------------------------------------------------
# `trace` config: structured span tracing (core.telemetry) on the smoke
# flagship.  Three in-process runs at the mesh smoke geometry — (1) an
# untimed warm-up that pays the one-time XLA builds, (2) a telemetry-OFF
# timed run, (3) a telemetry-ON timed run — then:
#
#   * exports the ON run's spans as Chrome trace-event JSON
#     (TRACE_r07_trace.json — load it in Perfetto / chrome://tracing);
#   * cross-checks the span-derived device-busy seconds against the flat
#     stage accumulator (must agree within 5% — same stage_add calls feed
#     both surfaces);
#   * asserts the fused task's stage_counts are IDENTICAL off vs on
#     (span emission must never perturb the accumulators);
#   * gates telemetry-off overhead < 1% of the OFF wall.  A direct
#     on-vs-off wall comparison at smoke scale has run-to-run variance
#     far above 1%, so the gate is a PROJECTION: the measured per-call
#     cost of a DISABLED stage_add (one attribute read on the off path),
#     times the run's total stage entries, against 1% of the off wall.
#
# Invoke with `python bench.py trace` (or BENCH_TRACE=1); writes
# TRACE_r07.json + TRACE_r07_trace.json.
# ---------------------------------------------------------------------------

def main_trace():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from cluster_tools_tpu.core import runtime as rt
    from cluster_tools_tpu.core import telemetry
    from cluster_tools_tpu.core.storage import file_reader

    base = "/tmp/ctt_bench_trace"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    lab, bnd = synthetic_instance(MESH_SHAPE, seed=0)
    store = os.path.join(base, "vol.n5")
    with file_reader(store) as f:
        ds = f.require_dataset("bmap", shape=bnd.shape,
                               chunks=MESH_BLOCK, dtype="uint8")
        ds[:] = np.round(bnd * 255).astype("uint8")
    n_dev = len(jax.devices())

    # 1. warm-up: pays the XLA builds so the timed runs compare
    #    steady-state dispatch, not compile noise
    run_mesh_chain(store, os.path.join(base, "warmup"), False, n_dev)

    # 2. telemetry OFF (the baseline wall the overhead gate protects)
    cn0 = rt.counts_snapshot()
    t_off, _, st_off = run_mesh_chain(
        store, os.path.join(base, "off"), False, n_dev)
    n_entries = sum(rt.counts_delta(cn0).values())
    assert not telemetry.enabled(), \
        "telemetry armed during the OFF run"

    # 3. telemetry ON via the global-config key (exercises the BlockTask
    #    wiring, not just the API)
    t_on, _, st_on = run_mesh_chain(
        store, os.path.join(base, "on"), False, n_dev,
        extra_global={"telemetry_enabled": True,
                      "telemetry_ring_size": 1 << 17})
    spans = telemetry.spans_snapshot()
    telemetry.configure(enabled=False)

    # span emission must not perturb the accumulators
    assert st_off["stage_counts"] == st_on["stage_counts"], \
        (st_off["stage_counts"], st_on["stage_counts"])

    # telemetry-off overhead projection (see header note)
    n_cal = 200_000
    t0 = time.perf_counter()
    for _ in range(n_cal):
        rt.stage_add("host-map", 0.0)
    per_call_s = (time.perf_counter() - t0) / n_cal
    projected_s = per_call_s * n_entries
    assert projected_s < 0.01 * t_off, (projected_s, t_off)

    here = os.path.dirname(os.path.abspath(__file__))
    trace_path = os.path.join(here, "TRACE_r07_trace.json")
    n_events = telemetry.export_chrome_trace(trace_path, spans)
    roll = telemetry.summary(wall=t_on)
    out = {
        "metric": "telemetry_trace_flagship",
        "shape": list(MESH_SHAPE),
        "block_shape": MESH_BLOCK,
        "devices": n_dev,
        "note": ("smoke flagship (per-block streamed path) traced with "
                 "core.telemetry; trace artifact is Chrome trace-event "
                 "JSON (open TRACE_r07_trace.json in Perfetto).  The "
                 "overhead gate is a projection — per-call disabled "
                 "stage_add cost x total stage entries — because a "
                 "direct on/off wall diff at smoke scale is noise"),
        "wall_off_s": round(t_off, 3),
        "wall_on_s": round(t_on, 3),
        "stage_entries": n_entries,
        "trace_events": n_events,
        "rollups": roll,
        "gates": {
            "stage_counts_unchanged": {
                "fused_counts": st_on["stage_counts"], "pass": True},
            "telemetry_off_overhead": {
                "per_call_ns": round(per_call_s * 1e9, 1),
                "projected_s": round(projected_s, 6),
                "budget_s": round(0.01 * t_off, 4),
                "bound_frac": 0.01, "pass": True},
        },
    }
    path = os.path.join(here, "TRACE_r07.json")
    write_config(path, out)
    print(json.dumps({
        "metric": out["metric"],
        "wall_off_s": out["wall_off_s"],
        "wall_on_s": out["wall_on_s"],
        "n_spans": roll["n_spans"],
        "trace_events": n_events,
        "overhead_projected_frac": round(projected_s / t_off, 6),
        "detail": os.path.basename(path)}))


# ---------------------------------------------------------------------------
# `serve` config: open-loop load harness against the resident server
# (ISSUE 16 tentpole 1).  Three stub-pipeline load levels (light / near
# saturation / overload) run THREADED — the real worker thread, real
# sleeps — so the committed BENCH_serve.json measures the serve path's
# actual queueing behaviour, plus one real-pipeline row (FusedROIPipeline
# at a small ROI geometry; XLA compile paid at startup via
# ensure_compiled, warm requests after).  Every row embeds the SLO
# engine's burn-rate report.
#
# `python bench.py serve --smoke` is the tier-1 path: the SAME schema,
# produced by the deterministic virtual-time mode, no XLA, no real
# sleeps — the smoke test asserts the schema without paying the load run.
# ---------------------------------------------------------------------------

# (offered_hz, n_requests) stub levels: the synthetic cost model
# (2 ms prepare + 4 ms/block + 1 ms tail, mean 3.4 blocks/request) puts
# capacity near 60 req/s — the ladder brackets it from both sides
SERVE_STUB_LEVELS = ((20.0, 200), (55.0, 300), (120.0, 300))
SERVE_SEED = 7


def _serve_spec(rate_hz, n_requests, smoke=False):
    from cluster_tools_tpu.core.loadgen import LoadSpec
    if smoke:
        # tiny but same shape: enough requests that every lane appears
        return LoadSpec(seed=SERVE_SEED, rate_hz=rate_hz,
                        n_requests=max(30, n_requests // 10),
                        n_tenants=20)
    return LoadSpec(seed=SERVE_SEED, rate_hz=rate_hz,
                    n_requests=n_requests, n_tenants=200)


def _serve_stub_row(rate_hz, n_requests, base, smoke):
    from cluster_tools_tpu.core import loadgen, slo
    spec = _serve_spec(rate_hz, n_requests, smoke)
    wd = os.path.join(base, f"stub_{int(rate_hz)}hz")
    eng = slo.SLOEngine()
    if smoke:
        row = loadgen.run_virtual(spec, wd, slo_engine=eng)
        row.pop("server", None)
        row.pop("schedule", None)
    else:
        row = loadgen.run_threaded(spec, wd, slo_engine=eng,
                                   metrics_path=None)
    row["pipeline"] = "synthetic"
    return row


def _serve_real_row(base):
    """One `slow` real-pipeline row: FusedROIPipeline at a small ROI
    geometry, low offered rate (the compile is paid before the clock
    starts)."""
    import jax  # noqa: F401  — fail fast if the device stack is absent

    from cluster_tools_tpu.core import loadgen, slo
    from cluster_tools_tpu.core.server import FusedROIPipeline

    shape = (16, 64, 64)
    pipe = FusedROIPipeline(shape, block_shape=(8, 32, 32),
                            halo=(2, 8, 8))
    pipe.ensure_compiled("uint8")
    rng = np.random.default_rng(SERVE_SEED)

    def volume_fn(arrival):
        # seeded per-request volumes at the server's ROI geometry
        return rng.integers(0, 256, size=shape, dtype=np.uint8)

    spec = loadgen.LoadSpec(seed=SERVE_SEED, rate_hz=2.0, n_requests=12,
                            n_tenants=4)
    eng = slo.SLOEngine()
    row = loadgen.run_threaded(spec, os.path.join(base, "real"),
                               pipeline=pipe, slo_engine=eng,
                               volume_fn=volume_fn, metrics_path=None)
    row["pipeline"] = "fused_roi"
    row["roi_shape"] = list(shape)
    return row


def main_serve():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    smoke = "--smoke" in sys.argv[1:]
    out_path = None
    argv = sys.argv[1:]
    if "--out" in argv:
        out_path = argv[argv.index("--out") + 1]
    base = "/tmp/ctt_bench_serve"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)

    rows = [_serve_stub_row(r, n, base, smoke)
            for r, n in SERVE_STUB_LEVELS]
    real_row = None
    if not smoke:
        real_row = _serve_real_row(base)

    import jax

    from cluster_tools_tpu.core import slo
    out = {
        "metric": "serve_load",
        "mode": "smoke-virtual" if smoke else "threaded",
        # the stub rows run no device program; only real_pipeline does
        "platform": jax.devices()[0].platform,
        "seed": SERVE_SEED,
        "note": ("open-loop Poisson load against the resident server: "
                 "latency charged from SCHEDULED arrival, so overload "
                 "compounds into the tail.  Stub levels bracket the "
                 "synthetic capacity (~60 req/s); the real-pipeline row "
                 "is warm (compile paid before the clock).  Single-core "
                 "emulated-mesh caveat applies: absolute latencies are "
                 "host-bound, the CURVES (saturation shape, lane "
                 "separation, burn rates) are the signal"),
        "slo_objectives": [o._asdict() for o in slo.default_objectives()],
        "burn_windows": [list(w) for w in slo.DEFAULT_WINDOWS],
        "stub_levels": rows,
        "real_pipeline": real_row,
    }
    from cluster_tools_tpu.core import telemetry
    out["memory"] = telemetry.memory_rollup()
    out["peak_rss_gb"] = round(telemetry.host_peak_rss_gb(), 2)
    if out_path is None and not smoke:
        here = os.path.dirname(os.path.abspath(__file__))
        out_path = os.path.join(here, "BENCH_serve.json")
    if out_path:
        write_config(out_path, out)
    print(json.dumps({
        "metric": out["metric"], "mode": out["mode"],
        "platform": out["platform"],
        "levels": [{"offered_hz": r["offered_hz"],
                    "throughput_hz": r["throughput_hz"],
                    "p99_edit_s": r["lanes"].get("edit", {}).get("p99_s"),
                    "overload": r.get("slo", {}).get("overload")}
                   for r in rows],
        "real": (None if real_row is None else {
            "throughput_hz": real_row["throughput_hz"],
            "served": real_row["served"]}),
        "detail": (os.path.basename(out_path) if out_path else None)}))


# ---------------------------------------------------------------------------
# `edits` config: interactive proofreading round-trip (ISSUE 19).
# One small watershed->multicut instance is solved through the real
# workflow chain, then a stream of merge/split edits runs through the
# resident server's edit lane WHILE a bulk tenant floods ROI requests.
# Gates asserted before the artifact is written: median edit round-trip
# < 0.5x a from-scratch re-solve of the same geometry; edits not starved
# (median edit queue-wait <= median bulk queue-wait); incremental and
# from-scratch re-solve of the edited problem produce identical
# assignments.  Same honesty caveat as BENCH_warm: 1-core emulated mesh,
# so absolute times are host-bound — the RATIOS are the signal.
# ---------------------------------------------------------------------------

EDITS_SEED = 19
EDITS_N_MERGE = 6
EDITS_N_SPLIT = 6


def _edits_instance(base, shape):
    """Solve one watershed->multicut instance (threads target: the edits
    path is host-side) and return its paths."""
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir
    from cluster_tools_tpu.core.storage import file_reader
    from cluster_tools_tpu.workflows.segmentation import (
        MulticutSegmentationWorkflow)
    from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

    config_dir = os.path.join(base, "configs")
    cfg = ConfigDir(config_dir)
    cfg.write_global_config({"block_shape": [10, 10, 10],
                             "max_num_retries": 0})
    cfg.write_task_config("watershed", {"threshold": 0.4,
                                        "size_filter": 8, "impl": "host"})
    _, bnd = synthetic_instance(shape, n_cells=max(
        int(np.prod(shape) / 6000), 6), seed=EDITS_SEED)
    path = os.path.join(base, "data.n5")
    with file_reader(path) as f:
        f.require_dataset("bmap", shape=shape, chunks=(10, 10, 10),
                          dtype="float32")[:] = bnd
    tmp_folder = os.path.join(base, "tmp")
    ws = WatershedWorkflow(
        input_path=path, input_key="bmap", output_path=path,
        output_key="ws", tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=2, target="threads")
    mc = MulticutSegmentationWorkflow(
        input_path=path, input_key="bmap", ws_path=path, ws_key="ws",
        problem_path=os.path.join(base, "problem.n5"), output_path=path,
        output_key="seg", tmp_folder=tmp_folder, config_dir=config_dir,
        max_jobs=2, target="threads", n_scales=1, dependency=ws)
    assert ctt.build([mc]), "instance build failed"
    return {"data": path, "problem": os.path.join(base, "problem.n5"),
            "assignments": os.path.join(tmp_folder,
                                        "multicut_assignments.npy")}


def _edit_pairs(session, table, n_pairs, same_segment):
    """Disjoint adjacent fragment pairs sharing >= 1 subproblem block,
    currently in the same (split candidates) / different (merge
    candidates) segment — deterministic scan over the s0 edge list."""
    used, out = set(), []
    for u, v in session.base_uv:
        ou, ov = int(session.s0_nodes[u]), int(session.s0_nodes[v])
        if ou == 0 or ov == 0 or ou in used or ov in used:
            continue
        if bool(table[ou] == table[ov]) != same_segment:
            continue
        if not session.affected_blocks([ou, ov]):
            continue
        out.append((ou, ov))
        used.update((ou, ov))
        if len(out) == n_pairs:
            break
    return out


def main_edits():
    import threading

    from cluster_tools_tpu.core import telemetry
    from cluster_tools_tpu.core.server import ResidentSegmentationServer
    from cluster_tools_tpu.edits import (EditLog, EditPipeline, EditSession,
                                         stable_relabel)

    smoke = "--smoke" in sys.argv[1:]
    argv = sys.argv[1:]
    out_path = argv[argv.index("--out") + 1] if "--out" in argv else None
    base = "/tmp/ctt_bench_edits"
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    shape = (24, 24, 24) if smoke else (40, 40, 40)
    paths = _edits_instance(base, shape)

    # baseline: from-scratch re-solve of the SAME geometry (every
    # subproblem cold + reduce + global), median of 3
    t_full = []
    for _ in range(3):
        s = EditSession(paths["problem"])
        t0 = time.perf_counter()
        s.solve(incremental=False)
        t_full.append(time.perf_counter() - t0)
    full_solve_s = float(np.median(t_full))

    probe = EditSession(paths["problem"])
    table0 = np.load(paths["assignments"])
    merges = _edit_pairs(probe, table0, EDITS_N_MERGE, same_segment=False)
    splits = _edit_pairs(probe, table0, EDITS_N_SPLIT, same_segment=True)
    edit_stream = [("merge", p) for p in merges] + \
        [("split", p) for p in splits]
    assert len(edit_stream) >= 5, "instance too merged to mine edit pairs"

    # the bulk tenant: a synthetic ROI pipeline (prepare + 4 blocks x
    # ~2 ms + tail) flooding the server at about its service rate, so
    # the queue sits near saturation while the edits arrive
    class _BulkStub:
        n_blocks = 4

        def prepare(self, volume):
            time.sleep(0.002)
            return {}

        def run_block(self, ctx, bid):
            time.sleep(0.002)
            return bid

        def finalize(self, ctx, block_results):
            time.sleep(0.001)
            return {"n_segments": 1}

    log = EditLog(os.path.join(base, "edits.jsonl"))
    session = EditSession(paths["problem"],
                          flight_dir=os.path.join(base, "flight"))
    pipe = EditPipeline(session, log, paths["assignments"],
                        ws_path=paths["data"], ws_key="ws",
                        output_path=paths["data"], output_key="seg")
    srv = ResidentSegmentationServer(os.path.join(base, "srv"),
                                     _BulkStub(), metrics_path="",
                                     lane_pipelines={"edit": pipe})
    srv.start()
    stop = threading.Event()

    def bulk_client():
        i = 0
        while not stop.is_set():
            try:
                srv.submit("bulk-tenant", f"ROI{i}")
            except RuntimeError:        # shutdown raced the last submit
                return
            i += 1
            time.sleep(0.004)

    flood = threading.Thread(target=bulk_client, daemon=True)
    flood.start()
    time.sleep(0.1)                     # let the bulk backlog form
    edit_rows = []
    for op, (a, b) in edit_stream:
        h = srv.submit("proofreader", {"op": op, "fragments": [a, b]},
                       lane="edit")
        res = h.result(300)
        edit_rows.append({
            "op": op, "fragments": [a, b], "edit_id": res["edit_id"],
            "round_trip_s": res["round_trip_s"],
            "affected_blocks": len(res["affected_blocks"]),
            "touched_blocks": len(res["touched_blocks"]),
            "changed_fragments": res["changed_fragments"]})
    stop.set()
    _, wait_hist, _ = srv.latency_histograms()
    bulk_served = srv.stats()["tenants_served"].get("bulk-tenant", 0)
    srv.shutdown(drain=False)
    flood.join(timeout=5)

    # identity gate: replaying the log from scratch (every cache
    # ignored) reproduces the served assignment table exactly
    final_table = np.load(paths["assignments"])
    scratch = EditSession(paths["problem"])
    scratch.replay(EditLog(log.path))
    labels_scr = scratch.solve(incremental=False)
    identity = bool(np.array_equal(
        stable_relabel(final_table, scratch.s0_nodes.astype("int64"),
                       labels_scr), final_table))

    rts = sorted(r["round_trip_s"] for r in edit_rows)
    median_rt = float(np.median(rts))
    ratio = median_rt / full_solve_s
    edit_p50 = wait_hist["edit"].quantile(0.5) if "edit" in wait_hist \
        else None
    bulk_p50 = wait_hist["bulk"].quantile(0.5) if "bulk" in wait_hist \
        else None
    not_starved = (edit_p50 is not None and bulk_p50 is not None
                   and edit_p50 <= bulk_p50)
    gates = {"ratio_lt_0_5": ratio < 0.5, "edit_not_starved": not_starved,
             "identity": identity}
    if not smoke:
        assert all(gates.values()), gates

    out = {
        "metric": "edit_roundtrip",
        "mode": "smoke" if smoke else "full",
        "seed": EDITS_SEED,
        "note": ("interactive proofreading round-trip on the resident "
                 "server's edit lane (submit -> resolve -> warm "
                 "incremental solve -> LUT patch -> touched-block "
                 "rewrite) while a bulk tenant floods ROI requests at "
                 "about the service rate.  full_solve_s is a from-"
                 "scratch re-solve of the SAME geometry (every "
                 "subproblem cold + reduce + global).  1-core emulated-"
                 "mesh caveat as in BENCH_warm: absolute times are "
                 "host-bound; the round-trip/full-solve ratio and the "
                 "per-lane queue-wait split are the signal"),
        "geometry": {
            "shape": list(shape), "block_shape": session.block_shape,
            "n_blocks": session.blocking.n_blocks,
            "n_fragments": int(len(session.s0_nodes)),
            "n_edges": int(len(session.base_uv))},
        "full_solve_s": full_solve_s,
        "full_solve_samples_s": t_full,
        "edits": edit_rows,
        "median_edit_round_trip_s": median_rt,
        "p90_edit_round_trip_s": float(rts[int(0.9 * (len(rts) - 1))]),
        "round_trip_over_full_solve": ratio,
        "counters": dict(session.counters),
        "queue_wait": {
            "edit_p50_s": edit_p50, "bulk_p50_s": bulk_p50,
            "edit": {str(k): v for k, v
                     in wait_hist["edit"].cumulative().items()}
            if "edit" in wait_hist else None,
            "bulk": {str(k): v for k, v
                     in wait_hist["bulk"].cumulative().items()}
            if "bulk" in wait_hist else None},
        "bulk_requests_served": int(bulk_served),
        "identity_incremental_equals_scratch": identity,
        "gates": gates,
    }
    out["memory"] = telemetry.memory_rollup()
    out["peak_rss_gb"] = round(telemetry.host_peak_rss_gb(), 2)
    if out_path is None and not smoke:
        here = os.path.dirname(os.path.abspath(__file__))
        out_path = os.path.join(here, "BENCH_edits.json")
    if out_path:
        write_config(out_path, out)
    print(json.dumps({
        "metric": out["metric"], "mode": out["mode"],
        "median_edit_round_trip_s": round(median_rt, 4),
        "full_solve_s": round(full_solve_s, 4),
        "ratio": round(ratio, 4),
        "edit_p50_wait_s": edit_p50, "bulk_p50_wait_s": bulk_p50,
        "gates": gates,
        "detail": (os.path.basename(out_path) if out_path else None)}))


# ---------------------------------------------------------------------------
# `trace-diff` config: the regression gate (ISSUE 16 tentpole 3).
# Compares two committed trace artifacts' rollups per stage and exits
# nonzero when a device-path quantity regresses past threshold — the
# before/after check every future perf PR runs against TRACE_r07.json
# (ROADMAP item 5's entry point).
# ---------------------------------------------------------------------------

def main_trace_diff(argv):
    import argparse

    from cluster_tools_tpu.core import telemetry

    p = argparse.ArgumentParser(
        prog="bench.py trace-diff",
        description="Gate on rollup regressions between two trace "
                    "artifacts (baseline vs candidate)")
    p.add_argument("baseline", help="baseline artifact (e.g. "
                                    "TRACE_r07.json) or bare rollups")
    p.add_argument("candidate", help="candidate artifact or bare rollups")
    p.add_argument("--rel-threshold", type=float, default=0.2,
                   help="relative worsening that regresses (default 0.2)")
    p.add_argument("--abs-floor-s", type=float, default=0.05,
                   help="absolute floor in seconds under which deltas "
                        "never regress (default 0.05)")
    p.add_argument("--mem-abs-floor-gb", type=float, default=0.25,
                   help="absolute floor in GiB under which peak-memory "
                        "deltas never regress (default 0.25)")
    args = p.parse_args(argv)

    def load_rollups(path):
        with open(path) as f:
            doc = json.load(f)
        # accept a full TRACE artifact or a bare rollups dict
        return doc.get("rollups", doc) if isinstance(doc, dict) else doc

    diff = telemetry.diff_rollups(
        load_rollups(args.baseline), load_rollups(args.candidate),
        rel_threshold=args.rel_threshold, abs_floor_s=args.abs_floor_s,
        mem_abs_floor_gb=args.mem_abs_floor_gb)
    print(json.dumps(diff, indent=1))
    sys.exit(1 if diff["regressed"] else 0)


def main_lint(argv):
    """Run the full ctt-lint analyzer and commit the report as a bench
    artifact (LINT_r18.json) — same schema family as BENCH_*/TRACE_*
    (identity via ``cmd: "lint"``), so artifact hygiene tests cover it."""
    from cluster_tools_tpu import analysis

    out = "LINT_r18.json"
    args = list(argv)
    if "--json" in args:
        out = args[args.index("--json") + 1]
        del args[args.index("--json"):args.index("--json") + 2]
    sys.exit(analysis.main(args + ["--json", out]))


if __name__ == "__main__":
    from cluster_tools_tpu.core.runtime import use_compile_cache

    use_compile_cache()
    if os.environ.get("BENCH_MESH") or "mesh" in sys.argv[1:]:
        main_mesh()
    elif os.environ.get("BENCH_WARM") or "warm" in sys.argv[1:]:
        main_warm()
    elif "lint" in sys.argv[1:]:
        main_lint([a for a in sys.argv[1:] if a != "lint"])
    elif "trace-diff" in sys.argv[1:]:
        main_trace_diff(
            [a for a in sys.argv[1:] if a != "trace-diff"])
    elif os.environ.get("BENCH_TRACE") or "trace" in sys.argv[1:]:
        main_trace()
    elif os.environ.get("BENCH_SERVE") or "serve" in sys.argv[1:]:
        main_serve()
    elif os.environ.get("BENCH_EDITS") or "edits" in sys.argv[1:]:
        main_edits()
    else:
        main()
