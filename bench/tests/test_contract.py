"""BENCHMARK.json and the files it names, and the entry point's refusal
to report anything off a TPU."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_sources():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert {"setup_s", "step_s"} <= e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    c = harness.load_cell(cell["name"], BENCH)
    assert cell["chips"] == 1
    assert set(c["limits"]) == {"loss_gap", "grad_gap", "delta_gap",
                                "grad_diff", "delta_diff"}
    dims = c["model"].dims(c["cfg"], c["traffic"])
    assert dims[0] == c["traffic"]["features"]
    assert dims[-1] == c["traffic"]["classes"]
    assert len(dims) == c["cfg"]["layers"] + 1
    for m in c["per_layer"]:
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name']}").read)


def test_configuration_files():
    for cfg in BENCH["configs"]:
        path = ROOT / cfg["file"]
        assert path.parts[len(ROOT.parts)] == "bench"
        doc = json.loads(path.read_text())
        assert doc["name"] == cfg["name"]
        assert (path.with_suffix(".py")).exists()


def _run(args, cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    return bool(lines) and '"correct"' in lines[-1]


def test_no_result_off_the_tpu(tmp_path):
    out = _run(["--workload", "gcn.arxiv", "--seed", "1", "--seconds", "1",
                "--trace", "1"], ROOT, tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "TPU" in out.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", "gcn.arxiv", "--seed", "1", "--seconds", "1"],
               tmp_path, tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
