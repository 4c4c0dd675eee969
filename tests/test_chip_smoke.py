"""The chip bring-up entry point and the compile-cache rule, on the CPU.

``chip_smoke.py --rehearse`` runs every phase at a tiny size through
the Pallas interpreter; without ``--rehearse`` and without a TPU it
must refuse to run and print no result.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_rehearse_runs_every_phase(tmp_path):
    out = _smoke(tmp_path, "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    records = [json.loads(line) for line in lines[:-1]]
    done = {r["phase"]: r["passed"] for r in records if r.get("done")}
    assert done == {"operators": True, "training": True, "serving": True}
    assert all(r["exact_int"] for r in records
               if r["phase"] == "operators" and "op" in r)
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 1


def test_chip_smoke_rehearse_four_chips(tmp_path):
    out = _smoke(tmp_path, "--rehearse", "--four-chips")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    records = [json.loads(line) for line in lines[:-1]]
    checks = {r["check"]: r for r in records if "check" in r}
    assert checks["plan_placement"]["ok"] is True
    assert all(g <= checks["loss_match"]["tol"]
               for g in checks["loss_match"]["rel_gap"].values())
    assert {r["layout"] for r in records if "layout" in r} == {
        "single", "replicated", "rowshard"}
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}


def test_chip_smoke_without_tpu_prints_no_result(tmp_path):
    out = _smoke(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache") == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
