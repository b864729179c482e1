"""The two-pass mutex-watershed cell: its configuration resolves to its
files, generates its 4D input and names its readers; a sound run is
``correct``, and the faults a two-pass chain can have are not.

Small sizes on the CPU: (24, 96, 96) in [12, 48, 48] blocks (2 x 2 x 2, a
pass-2 block touching every pass-1 block), halo [2, 8, 8], the whole
volume compared.  The CPU backend would take the host path, so the test
forces ``impl`` ``device``, the path the chip runs."""

import os

import numpy as np
import pytest

import affinities
import mws_affinities
import run

CELL = "cremi_a_mws.clean"
SEED = 2 ** 33 + 77


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


def small(bench):
    wl, cfg, mix, per_layer = run.resolve(bench, CELL)
    halo = [2, 8, 8]
    cfg = dict(cfg, shape=[24, 96, 96],
               global_config=dict(cfg["global_config"],
                                  block_shape=[12, 48, 48]),
               task_configs={k: dict(v, impl="device")
                             for k, v in cfg["task_configs"].items()},
               workflow=dict(cfg["workflow"], kwargs=dict(
                   cfg["workflow"]["kwargs"], halo=halo)),
               reference=dict(cfg["reference"], halo=halo))
    return wl, cfg, mix


def test_configuration_resolves_generates_and_names_readers(bench,
                                                            tmp_path):
    from cluster_tools_tpu.models.unet import DEFAULT_OFFSETS

    wl, cfg, mix, per_layer = run.resolve(bench, CELL)
    gen, args = run.input_spec(cfg)
    assert gen is mws_affinities
    assert [tuple(o) for o in args["offsets"]] == list(DEFAULT_OFFSETS)
    assert cfg["workflow"]["kwargs"]["offsets"] == args["offsets"]
    assert cfg["shape"] == [100, 1024, 1024] and cfg["reduced"] == ["shape"]
    assert {m["name"] for m in wl["e2e"]} == {"fragment_voxels_per_s",
                                              "setup_s"}
    names = {m["name"] for m in per_layer}
    assert {"stage_s.host_scan.mws", "scan_ns_per_edge.mws",
            "mws_sort_ms_per_block", "device_idle_share.fragments",
            "idle_s.host.fragments", "idle_s.store.fragments",
            "stage_s.store_write.fragments"} <= names
    for name in names:
        path = os.path.join(run.HERE, "metrics", name + ".py")
        assert callable(run.load_module(path, name).read)
    _, small_cfg, _ = small(bench)
    vol = run.make_input(small_cfg, mix, SEED, str(tmp_path / "in.n5"))
    assert vol.shape == (12, 24, 96, 96) and vol.dtype == np.uint8


def test_readers_find_nothing_in_a_program_without_them():
    """A traced run of a program that records no scan edges and no mws
    scopes (the parent's): the new readers return None and raise not."""
    chain = {"workdir": "/nonexistent/chain0", "status": {
        "mws_pass1": {"stages": {"host-scan": 3.0}, "stage_counts": {}},
        "write_two_pass_mws": {"stages": {"store-write": 1.0}}}}
    run_ = {"chains": [chain], "trace": {"n_devices": 1},
            "blocks_per_chain": 8}
    load = run.load_module
    here = os.path.join(run.HERE, "metrics")
    assert load(os.path.join(here, "scan_ns_per_edge.mws.py"),
                "scan_ns_per_edge.mws").read(run_) is None
    assert load(os.path.join(here, "mws_sort_ms_per_block.py"),
                "mws_sort_ms_per_block").read(run_) is None
    assert load(os.path.join(here, "stage_s.host_scan.mws.py"),
                "stage_s.host_scan.mws").read(run_) == 3.0


def test_input_is_the_affinities_generators(bench):
    _, cfg, mix, _ = run.resolve(bench, CELL)
    offsets = cfg["input"]["args"]["offsets"]
    assert np.array_equal(
        mws_affinities.generate((8, 40, 40), SEED, mix, offsets),
        affinities.generate((8, 40, 40), SEED, mix, offsets))


def test_program_without_the_packed_scan_stops_before_any_input(
        bench, monkeypatch, tmp_path):
    """The parent's program: the run exits at once, before it generates
    or writes anything, instead of running chains no run can hold."""
    from cluster_tools_tpu import native

    monkeypatch.delattr(native, "mutex_clustering_packed")
    monkeypatch.setattr(affinities, "generate", None)
    _, cfg, mix = small(bench)
    with pytest.raises(SystemExit) as e:
        run.make_input(cfg, mix, SEED, str(tmp_path / "in.n5"))
    assert e.value.code != 0
    assert not os.path.exists(tmp_path / "in.n5")


def test_scan_ns_per_edge_reads_the_counter():
    chain = {"status": {
        "mws_pass1": {"stages": {"host-scan": 2.0},
                      "stage_counts": {"scan-edges": 10 ** 9}},
        "mws_pass2": {"stages": {"host-scan": 4.0},
                      "stage_counts": {"scan-edges": 2 * 10 ** 9}},
        "find_uniques_two_pass_mws": {"stages": {"host-scan": 9.0}}}}
    reader = run.load_module(
        os.path.join(run.HERE, "metrics", "scan_ns_per_edge.mws.py"),
        "scan_ns_per_edge.mws")
    assert reader.read({"chains": [chain, chain]}) == pytest.approx(2.0)


def test_reference_builds_once_from_many_threads(tmp_path, monkeypatch):
    """A fresh checkout: the reference's blocks start on several threads
    at once, and the C++ is built by the first and loaded by all."""
    from concurrent.futures import ThreadPoolExecutor

    from refs import mws_two_pass as ref

    monkeypatch.setattr(ref, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(ref, "_LIB", None)
    with ThreadPoolExecutor(4) as pool:
        libs = list(pool.map(lambda _: ref.library(), range(4)))
    assert all(lib is libs[0] for lib in libs)
    assert os.listdir(tmp_path / "work" / "refs") == [
        os.path.basename(libs[0]._name)]


def merge_two_largest(labels):
    ids, counts = np.unique(labels, return_counts=True)
    a, b = ids[np.argsort(-counts)[:2]]
    return np.where(labels == b, a, labels)


def plant(monkeypatch, fault):
    from cluster_tools_tpu.ops import mws
    from cluster_tools_tpu.workflows import mutex_watershed as mw

    if fault == "unseeded_pass2":
        monkeypatch.setattr(mw.MwsPass2, "seeded", False)
    elif fault == "assignments_dropped":
        real = mw.TwoPassAssignments.process_job.__func__

        def process_job(cls, job_id, job_config, log_fn):
            tmp = job_config["config"]["tmp_root"]
            for name in os.listdir(tmp):
                if name.startswith("mws_two_pass_assignments_block_"):
                    os.remove(os.path.join(tmp, name))
            return real(cls, job_id, job_config, log_fn)

        monkeypatch.setattr(mw.TwoPassAssignments, "process_job",
                            classmethod(process_job))
    elif fault == "block_altered":
        real = mws.mutex_watershed_scan_sorted
        calls = []

        def scan(*a, **kw):
            # the warm-up chain scans one block; the window's first block
            # scanned is the second call
            labels = real(*a, **kw)
            calls.append(1)
            return merge_two_largest(labels) if len(calls) == 2 else labels

        monkeypatch.setattr(mws, "mutex_watershed_scan_sorted", scan)
    else:
        raise ValueError(fault)


def run_small(bench):
    wl, cfg, mix = small(bench)
    return run.execute(CELL, wl, cfg, mix, [], SEED, 0.1, 0,
                       require_chip=False)


def test_sound_run_is_correct(bench):
    res = run_small(bench)
    assert res["correct"] is True
    assert res["checks"]["split_segments"]["value"] == 0
    assert res["checks"]["merged_segments"]["value"] == 0


@pytest.mark.parametrize("fault", ["unseeded_pass2", "assignments_dropped",
                                   "block_altered"])
def test_fault_is_not_correct(bench, monkeypatch, fault):
    plant(monkeypatch, fault)
    res = run_small(bench)
    assert res["correct"] is False
    assert (res["checks"]["split_segments"]["value"]
            + res["checks"]["merged_segments"]["value"]) > 0


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, SEED])
def test_control_is_not_correct(bench, monkeypatch, seed):
    """The control through the harness's path: the reference at 7-bit
    affinities put in the program's place reads false."""
    import importlib

    import control

    wl, cfg, mix = small(bench)
    ref = importlib.import_module("refs." + cfg["reference"]["name"])
    monkeypatch.setattr(ref, "compare", ref.compare)
    control.plant_control(ref)
    res = run.execute(CELL, wl, cfg, mix, [], seed, 0.1, 0,
                      require_chip=False)
    assert res["correct"] is False
    assert (res["checks"]["split_segments"]["value"]
            + res["checks"]["merged_segments"]["value"]) > 0
