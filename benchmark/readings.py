#!/usr/bin/env python3
"""The readings a cell's limits are set from: the numbers ``correct``
compares, for the program and for the control, over many seeds in one
process.

    python3 benchmark/readings.py --workload <cell> --seeds <n> [<n> ...]

For each seed: the input from the seed, one whole chain of the cell's
workflow (the first loads or compiles every program), the comparison with
the reference (the program's reading), then the bfloat16 reference put in
the program's place in the chain's store and compared again (the control's
reading).  One JSON line per seed on stdout.  The benchmark's own runs never
run this.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time

import control
import run


def read(cfg, mix, seeds, workers=None):
    """Yield one dict of readings per seed."""
    from cluster_tools_tpu.workflows import fused_pipeline

    ref = importlib.import_module("refs." + cfg["reference"]["name"])
    work = os.path.join(run.WORK, "readings")
    for seed in seeds:
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        input_path = os.path.join(work, "input.n5")
        vol = run.make_input(cfg, mix, seed, input_path)
        chain = os.path.join(work, "chain")
        wall, _ = run.run_chain(cfg, input_path, chain)
        fused_pipeline.clear_caches()
        t = time.perf_counter()
        picked, sound = ref.reference_blocks(vol, cfg, seed, "float32",
                                             workers)
        program = ref.score(picked, sound, [chain], cfg)
        _, low = ref.reference_blocks(vol, cfg, seed, "bfloat16", workers)
        control.write_control(picked, low, [chain], cfg)
        yield {"seed": seed, "chain_s": wall,
               "reference_s": time.perf_counter() - t,
               "program": program,
               "control": ref.score(picked, sound, [chain], cfg)}
    shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 3
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, cfg, mix, _ = run.resolve(bench, args.workload)
    run.use_cache()
    for r in read(cfg, mix, args.seeds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
