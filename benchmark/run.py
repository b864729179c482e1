#!/usr/bin/env python3
"""The benchmark harness: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file (``benchmark/configs/<config>.json``: the deployment,
its input, its workflow and task configs, the reference and the limits of
what ``correct`` compares), the traffic mix
(``benchmark/traffic/<mix>.json``), the reference
(``benchmark/refs/<name>.py``) and one reader per per-layer metric
(``benchmark/metrics/<metric>.py``).

The configuration names its input under an optional ``"input"`` object:
``"generator"``, a module ``benchmark/<generator>.py`` (default
``worley``, the boundary map), whose ``generate(shape, seed, mix,
**args)`` makes the array from the seed and the mix; and ``"args"``,
those keyword arguments (default none).  The array is stored in chunks of
the block, one chunk per channel along any leading axis it has beyond the
configuration's spatial ``shape``.

Set-up (``setup_s``, from process start to the window's start): device
generation of the input from ``--seed``, its N5 write, and one warm-up chain
(on a one-block ROI of the same volume where the configuration says so)
that loads or compiles every program the window runs.  The window runs
whole chains back to back, each into a fresh workdir, and closes at the end
of the first chain that ends at or after ``--seconds``.  With ``--trace 1``
the window runs under the JAX profiler and the per-layer metrics are read
from the task status files and the trace.  After the window the reference
recomputes a seeded sample of blocks and the chains' own store outputs are
compared with it.  The last line of stdout is one JSON object.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")
sys.path.insert(0, HERE)


class NoChip(Exception):
    pass


def say(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def rss_gib():
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, cell):
    """(workload entry, configuration dict, mix dict, per-layer metric
    entries of this cell) for ``cell``, all found by name; the workload
    entry carries the cell's end-to-end metric entries under ``e2e``."""
    import worley

    wl = {w["name"]: w for w in bench["workloads"]}.get(cell)
    if wl is None:
        raise SystemExit(f"unknown workload {cell!r}; have "
                         f"{sorted(w['name'] for w in bench['workloads'])}")
    centry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg = load_json(os.path.join(ROOT, centry["file"]))
    mix = worley.load_mix(wl["traffic"])
    per_layer = [m for m in bench["per_layer"]
                 if cell in m.get("workloads", [cell])]
    wl = dict(wl, e2e=[m for m in bench["end_to_end"]
                       if cell in m.get("workloads", [cell])])
    return wl, cfg, mix, per_layer


def input_spec(cfg):
    """(generator module, its keyword arguments) that the configuration's
    ``"input"`` names; with no ``"input"``, the boundary map of ``worley``.
    The module is imported by name from ``benchmark/``, so that worker
    processes can unpickle from it."""
    spec = cfg.get("input", {})
    name = spec.get("generator", "worley")
    if not (name.isidentifier()
            and os.path.isfile(os.path.join(HERE, name + ".py"))):
        raise SystemExit(f"configuration {cfg['name']!r}: no generator "
                         f"benchmark/{name}.py")
    return importlib.import_module(name), dict(spec.get("args", {}))


def make_input(cfg, mix, seed, path, setup=None):
    """Generate the configuration's input from ``seed`` and write it as the
    N5 dataset ``cfg["input_key"]`` under ``path``; returns the array.  The
    seconds of each part go into ``setup`` (``generate_s``, ``store_s``)."""
    import n5

    setup = {} if setup is None else setup
    gen, args = input_spec(cfg)
    block = tuple(cfg["global_config"]["block_shape"])
    t = time.perf_counter()
    vol = gen.generate(tuple(cfg["shape"]), seed, mix, **args)
    setup["generate_s"] = time.perf_counter() - t
    chunks = (1,) * (vol.ndim - len(block)) + block  # one per channel
    t = time.perf_counter()
    n5.write(path, cfg["input_key"], vol, chunks)
    setup["store_s"] = time.perf_counter() - t
    return vol


class TaskSpans(logging.Handler):
    """Host spans ``bench.task.<task>`` in the profiler trace, one per task
    of a chain: ``core.workflow.build`` logs "running task <id>" as it
    starts each task, in the thread that runs it; the span lasts until
    the next task starts or the chain ends."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.open = None

    def emit(self, record):
        if record.msg != "running task %s":
            return
        self.close()
        task_id = str(record.args[0])
        name = os.path.basename(task_id.split(":", 1)[-1])
        if name.endswith(".status"):
            name = name[:-len(".status")]
        import jax

        self.open = jax.profiler.TraceAnnotation(f"bench.task.{name}")
        self.open.__enter__()

    def close(self):
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits (JAX's own
    monitoring events) from the moment it is reset."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.compiles = self.cache_hits = 0


def fill(obj, subs):
    """Substitute ``{input}``-style placeholders in a JSON value."""
    if isinstance(obj, str):
        return obj.format(**subs)
    if isinstance(obj, dict):
        return {k: fill(v, subs) for k, v in obj.items()}
    if isinstance(obj, list):
        return [fill(v, subs) for v in obj]
    return obj


def run_chain(cfg, input_path, workdir, roi=None):
    """One whole chain of the configuration's workflow, from store read to
    the final store write, in a fresh ``workdir``.  Returns its wall time
    and the task status files it left."""
    import cluster_tools_tpu as ctt
    from cluster_tools_tpu.core.config import ConfigDir

    shutil.rmtree(workdir, ignore_errors=True)
    config_dir = os.path.join(workdir, "configs")
    cd = ConfigDir(config_dir)
    gconf = dict(cfg["global_config"])
    if roi is not None:
        gconf.update(roi_begin=list(roi[0]), roi_end=list(roi[1]))
    cd.write_global_config(gconf)
    for task, tconf in cfg["task_configs"].items():
        cd.write_task_config(task, tconf)
    wf = cfg["workflow"]
    subs = {"input": input_path, "out": os.path.join(workdir, "out.n5"),
            "work": workdir}
    task = getattr(ctt, wf["class"])(
        tmp_folder=os.path.join(workdir, "tmp"), config_dir=config_dir,
        max_jobs=int(wf.get("max_jobs", os.cpu_count() or 1)),
        target=wf["target"], **fill(wf["kwargs"], subs))
    t0 = time.perf_counter()
    ctt.build([task], raise_on_failure=True)
    wall = time.perf_counter() - t0
    status = {}
    tmp = os.path.join(workdir, "tmp")
    for name in sorted(os.listdir(tmp)):
        if name.endswith(".status"):
            st = load_json(os.path.join(tmp, name))
            status[st.get("task", name[:-7])] = st
    return wall, status


def use_cache():
    """JAX's persistent compile cache (and the program's executable tier)
    at a fixed path inside the checkout, whatever the environment names,
    so that only a checkout's first run compiles and two checkouts share
    nothing.  Returns the path."""
    import jax

    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from cluster_tools_tpu.core.runtime import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(jax, chips):
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def execute(cell, wl, cfg, mix, per_layer, seed, seconds, trace,
            require_chip=True):
    """One run of ``cell``; returns the result dict (the printed line).
    ``require_chip=False`` is for the tests, on the CPU."""
    import jax

    chips = int(wl["chips"])
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
        peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if devs[0].device_kind not in peaks:
            raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                         "benchmark/peaks.json")
    say(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{use_cache()}")
    counter = CompileCounter()
    work = os.path.join(WORK, cell)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    shape = tuple(cfg["shape"])
    block = tuple(cfg["global_config"]["block_shape"])
    n_vox = 1
    n_blocks = 1
    for s, b in zip(shape, block):
        n_vox *= s
        n_blocks *= -(-s // b)
    setup = {}

    input_path = os.path.join(work, "input.n5")
    vol = make_input(cfg, mix, seed, input_path, setup)
    t = time.perf_counter()
    roi = None
    if cfg["warmup"] == "roi":
        roi = ((0,) * len(shape), block)
    run_chain(cfg, input_path, os.path.join(work, "warmup"), roi)
    shutil.rmtree(os.path.join(work, "warmup"), ignore_errors=True)
    setup["warmup_s"] = time.perf_counter() - t
    setup["warmup_compiles"] = counter.compiles
    setup["warmup_cache_hits"] = counter.cache_hits
    gc.collect()
    # the window starts with no dirty pages: what set-up (or an earlier
    # run) wrote is not flushed inside it
    t = time.perf_counter()
    os.sync()
    setup["sync_s"] = time.perf_counter() - t

    trace_dir = os.path.join(work, "trace")
    counter.reset()
    spans = None
    if trace:
        jax.profiler.start_trace(trace_dir)
        spans = TaskSpans()
        wf_log = logging.getLogger("cluster_tools_tpu")
        wf_log.setLevel(logging.INFO)
        wf_log.addHandler(spans)
    t_w0 = time.perf_counter()
    setup_s = t_w0 - T_START
    chains = []
    failed = 0
    while True:
        i = len(chains)
        wd = os.path.join(work, f"chain{i}")
        t_c = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.chain{i}"):
            try:
                wall, status = run_chain(cfg, input_path, wd)
                chains.append({"workdir": wd, "wall_s": wall, "ok": True,
                               "status": status,
                               "t0": t_c - t_w0})
            except Exception:
                failed += 1
                say(f"chain {i} raised:\n{traceback.format_exc()}")
                chains.append({"workdir": wd, "ok": False,
                               "wall_s": time.perf_counter() - t_c,
                               "status": {}, "t0": t_c - t_w0})
            if spans is not None:
                spans.close()
        if time.perf_counter() - t_w0 >= seconds:
            break
    window_s = time.perf_counter() - t_w0
    if trace:
        logging.getLogger("cluster_tools_tpu").removeHandler(
            spans)
        jax.profiler.stop_trace()
    window_compiles, window_hits = counter.compiles, counter.cache_hits
    device = device_info(jax, chips)
    ok_chains = [c for c in chains if c["ok"]]
    say(f"set-up {setup_s} s: {setup}")
    say(f"window {window_s} s: {len(chains)} chains, {failed} failed, "
        f"walls {[c['wall_s'] for c in chains]}")
    for c in ok_chains[:1]:
        say("first chain's tasks: " + ", ".join(
            f"{name} {st.get('wall_time')} s" for name, st in sorted(
                c["status"].items(), key=lambda kv: -kv[1].get(
                    "wall_time", 0.0))))
    say(f"compiles inside the window: {window_compiles} "
        f"(persistent-cache hits {window_hits}); host peak RSS "
        f"{rss_gib()} GiB")

    result = {"correct": False, "attempted": len(chains), "failed": failed}
    if trace:
        import trace_reduce

        red = trace_reduce.reduce_dir(trace_dir, n_devices=chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = window_s
        run = {"chains": ok_chains, "trace": red, "config": cfg,
               "window_s": window_s,
               "blocks_per_chain": n_blocks}
        metrics = {}
        for m in per_layer:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"), m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": red["top_ops"],
            "idle_gaps": trace_reduce.label_gaps(red)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        # every voxel of every chain that finished in the window, over the
        # window's wall time; and the set-up time
        values = {"voxels/s": len(ok_chains) * n_vox / window_s,
                  "s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["unit"]],
                                         "unit": m["unit"]}
                             for m in wl["e2e"]}
    result["device"] = device

    # the comparison with the plain reference, after the window, with the
    # program's host caches released
    from cluster_tools_tpu.workflows import fused_pipeline

    fused_pipeline.clear_caches()
    gc.collect()
    t = time.perf_counter()
    # imported by module name (not by path) so that its worker processes
    # can unpickle the function they run
    ref = importlib.import_module("refs." + cfg["reference"]["name"])
    numbers = ref.compare(vol, [c["workdir"] for c in ok_chains], cfg, seed)
    limits = cfg["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(ok_chains) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    say(f"reference comparison took {time.perf_counter() - t} s")
    result["correct"] = correct
    result["checks"] = checks
    for k, c in checks.items():
        say(f"check {k}: {c['value']} (limit {c['limit']})")
    shutil.rmtree(work, ignore_errors=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl, cfg, mix, per_layer = resolve(bench, args.workload)
    if not args.trace:
        per_layer = []
    try:
        result = execute(args.workload, wl, cfg, mix, per_layer, args.seed,
                         args.seconds, args.trace)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
