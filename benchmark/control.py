#!/usr/bin/env python3
"""The control, through the harness's own ``correct``.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py`` does, except that once the window has closed,
every output the comparison reads (each sampled block of each chain's store)
is overwritten with the reference computed in bfloat16: the reference put in
the program's place, in the precision below the configuration's.  The
printed line must read ``"correct": false``.  The benchmark's own runs never
run this.
"""

import importlib
import os
import sys

import run


def write_control(picked, outs, chain_dirs, cfg):
    """Overwrite what the comparison reads in each chain's store with the
    bfloat16 reference's outputs ``outs`` of the ``picked`` blocks."""
    import n5

    for d in chain_dirs:
        for (begin, _), out in zip(picked, outs):
            for name, key in cfg["reference"]["outputs"].items():
                n5.write_region(os.path.join(d, "out.n5"), key, begin,
                                out[name])


def plant_control(ref):
    """Make ``ref.compare`` put the control in the program's place first."""
    real = ref.compare

    def compare(vol, chain_dirs, cfg, seed, precision="float32",
                workers=None):
        picked, outs = ref.reference_blocks(vol, cfg, seed, "bfloat16",
                                            workers)
        write_control(picked, outs, chain_dirs, cfg)
        return real(vol, chain_dirs, cfg, seed, precision, workers)

    ref.compare = compare


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = argv[argv.index("--workload") + 1]
    _, cfg, _, _ = run.resolve(bench, cell)
    plant_control(importlib.import_module("refs." + cfg["reference"]["name"]))
    return run.main(argv + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
