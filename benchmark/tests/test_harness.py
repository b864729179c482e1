"""Harness self-test: every cell resolves to its files by name, every name
and unit keeps to the contract's alphabet, and the command refuses to run
anywhere but on the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_workload_resolves(bench):
    for wl in bench["workloads"]:
        w, cfg, mix, per_layer = run.resolve(bench, wl["name"])
        assert cfg["name"] == wl["config"]
        assert os.path.exists(os.path.join(run.HERE, "traffic",
                                           wl["traffic"] + ".json"))
        assert os.path.exists(os.path.join(run.HERE, "refs", cfg[
            "reference"]["name"] + ".py"))
        assert per_layer, f"{wl['name']} reports no per-layer metric"
        for m in per_layer:
            path = os.path.join(run.HERE, "metrics", m["name"] + ".py")
            assert hasattr(run.load_module(path, m["name"]), "read")


def test_every_configuration_names_a_generator(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            gen, args = run.input_spec(json.load(f))
        assert callable(getattr(gen, "generate", None)), gen.__name__
        assert isinstance(args, dict)


def test_config_files_are_unique_and_under_paths(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        # run.py computes an end-to-end metric from its unit
        assert m["unit"] in ("voxels/s", "s") and m["source"] == "host_clock"
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for cell in cells:
        assert sum(cell in m.get("workloads", cells)
                   for m in e2e.values()) >= 2
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "cremi_a_watershed.clean", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_chip():
    out = _run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
