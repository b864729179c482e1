"""Chip smoke test: the fused multicut flagship, end to end, on a TPU.

Drives the main path once, through the entry points a user calls (README
"Usage", ``bench.run_chain``): ``ctt.build([MulticutSegmentationWorkflow(
..., target="tpu", n_scales=1, fused=True)])``.  Inside the chain the input
volume is uploaded once, the AOT-compiled per-block resident program runs
watershed, relabel, RAG and edge features, the host C++ multicut solves,
and the pooled final write stores the segmentation.

The data is a synthetic CREMI-like instance, (100, 1024, 1024) uint8 in the
reference's [50, 512, 512] blocks (8 full blocks, ~105 Mvox), generated from
``--seed`` and written as an N5 store under ``<checkout>/.smoke_work``.
The run fails (non-zero exit) unless

* the segmentation scores adapted Rand error <= 0.10 and VOI split+merge
  <= 0.6 against the generating ground truth;
* no block took a capacity-overflow redo (``host-fallback`` and
  ``cap-retry`` stage counts are 0);
* the first-party C++ solver library loaded.  It is built on demand with
  g++ from ``cluster_tools_tpu/native/src/solvers.cpp`` and nothing else;
* the resident program was compiled exactly once (``compiles`` 1 on a cold
  cache, 0 with one disk hit on a warm one) and contains the Pallas EDT
  (``tpu_custom_call``).

``--chips 4`` runs only the mesh-resident flagship over 4 chips and the
streamed one-chip run it is compared with: VOI delta <= 0.01, one sharded
program, one ``sync-execute`` wait, and no fall back to the streamed path.

One process holds the chip(s); nothing here starts a child that touches
JAX.  Both compile caches live in ``JAX_COMPILATION_CACHE_DIR`` when it is
set, else in ``<checkout>/.jax_cache`` (``runtime.use_compile_cache``).
Every number printed is a smoke number, not a benchmark number.  The last
line of stdout is the one JSON result; nothing else is printed there.
"""

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SHAPE = (100, 1024, 1024)
ARE_MAX = 0.10
VOI_MAX = 0.6
VOI_DELTA_MAX = 0.01   # tests/test_mesh_resident.py's parity bound


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(f"smoke: {msg}", flush=True)


def scores(gt, seg):
    """(VOI split + merge, adapted Rand error) against the ground truth."""
    from cluster_tools_tpu.utils.validation import (ContingencyTable,
                                                    cremi_score_from_table)

    vs, vm, are, _ = cremi_score_from_table(
        ContingencyTable.from_arrays_chunked(gt, seg))
    return float(vs) + float(vm), float(are)


def chain(bench, store, workdir, n_vox, fused_overrides=None):
    """One flagship run; returns (seg, fused_segmentation status)."""
    wall, seg = bench.run_chain(store, SHAPE, workdir, "tpu",
                                fused_overrides=fused_overrides)
    with open(os.path.join(workdir, "tmp",
                           "fused_segmentation.status")) as f:
        status = json.load(f)
    say(f"chain wall {wall} s, {n_vox / wall} voxels/s")
    for task_wall, task, *_ in bench.task_profile(workdir):
        say(f"  task {task}: {task_wall} s")
    say(f"  fused_segmentation sync-compile "
        f"{status['stages'].get('sync-compile')} s; stage_counts "
        f"{status['stage_counts']}; exec_cache {status['exec_cache']}")
    return seg, status


def compiled_once(status):
    """The task compiled its program once, or loaded it from the disk tier
    once.  Returns "cold" or "warm"."""
    ec = status["exec_cache"]
    compiles, disk_hits = ec.get("compiles", 0), ec.get("disk_hits", 0)
    check(compiles + disk_hits == 1,
          f"expected one compile or one disk load, got {ec}")
    return "cold" if compiles else "warm"


def one_chip(bench, work, lab, store, n_vox):
    from cluster_tools_tpu.core import runtime

    seg, status = chain(bench, store, os.path.join(work, "chain"), n_vox)
    counts = status["stage_counts"]
    for redo in ("host-fallback", "cap-retry"):
        check(counts.get(redo, 0) == 0,
              f"{counts.get(redo)} blocks took the {redo} redo")
    say(f"resident program cache: {compiled_once(status)}")
    resident = [ent for key, ent in runtime._EXEC_CACHE.items()
                if key[0] == "resident"]
    check(len(resident) == 1, f"{len(resident)} resident programs cached")
    check("tpu_custom_call" in resident[0].as_text(),
          "the resident program holds no Pallas kernel (tpu_custom_call)")
    voi, are = scores(lab, seg)
    say(f"VOI split+merge {voi}, adapted Rand error {are}")
    check(are <= ARE_MAX, f"adapted Rand error {are} > {ARE_MAX}")
    check(voi <= VOI_MAX, f"VOI {voi} > {VOI_MAX}")


def four_chips(bench, work, lab, store, n_vox):
    import jax

    check(len(jax.devices()) >= 4, f"--chips 4 found {len(jax.devices())}")
    say("streamed per-block flagship on one chip")
    seg_b, _ = chain(bench, store, os.path.join(work, "streamed"), n_vox)
    say("mesh-resident flagship over 4 chips")
    seg_m, st_m = chain(bench, store, os.path.join(work, "mesh"), n_vox,
                        {"mesh_resident": True, "mesh_shards": 4})
    counts = st_m["stage_counts"]
    check(counts.get("mesh-fallback", 0) == 0,
          "the mesh-resident task fell back to the streamed path")
    check(counts.get("sync-execute") == 1,
          f"{counts.get('sync-execute')} sync-execute waits, expected 1")
    say(f"sharded program cache: {compiled_once(st_m)}")
    (voi_b, are_b), (voi_m, are_m) = scores(lab, seg_b), scores(lab, seg_m)
    say(f"streamed VOI {voi_b} ARE {are_b}; mesh VOI {voi_m} ARE {are_m}")
    check(abs(voi_b - voi_m) <= VOI_DELTA_MAX,
          f"mesh vs streamed VOI delta {abs(voi_b - voi_m)} > "
          f"{VOI_DELTA_MAX}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this smoke test runs only on the "
              "chip", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from cluster_tools_tpu.core.runtime import use_compile_cache

    say(f"device_kind {devs[0].device_kind}, {len(devs)} devices; "
        f"compile cache {use_compile_cache()}")
    import bench
    from cluster_tools_tpu import native

    work = os.path.join(HERE, ".smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    try:
        check(native._load() is not None,
              "the native C++ solver library did not build or load")
        t0 = time.perf_counter()
        lab, bnd = bench.synthetic_instance(SHAPE, seed=args.seed)
        store = os.path.join(work, "vol.n5")
        bench.write_store(store, bnd)
        del bnd
        n_vox = lab.size
        say(f"generated and stored {SHAPE} instance (seed {args.seed}) in "
            f"{time.perf_counter() - t0} s")
        (four_chips if args.chips == 4 else one_chip)(
            bench, work, lab, store, n_vox)
        peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        say(f"peak device memory {peak / 2**30} GiB")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
