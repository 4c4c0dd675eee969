"""A whole run of each configuration at a tiny size on the CPU (Pallas
interpreter): the program against the float32 reference, the planted
faults that ``correct`` has to catch, and the control."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, check, harness

TINY = {"family": "power_law", "graph_seed": 0,
        "graph": {"nodes": 256, "edges": 1500, "pareto_shape": 1.5},
        "features": 16, "classes": 4}
WORKLOADS = ["gcn.arxiv", "agnn.arxiv"]


def tiny_cell(workload):
    c = harness.load_cell(workload,
                          harness.load_json(harness.ROOT / "BENCHMARK.json"))
    c["traffic"] = dict(TINY)
    c["cfg"] = dict(c["cfg"], hidden=16)
    return c


def run(c):
    return harness.run(c, 2**31 + 7, 0.0, False, time.perf_counter(),
                       jax.devices())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_program_matches_reference(workload):
    res = run(tiny_cell(workload))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for k, v in res["checks"].items():
        # Interpreted kernels in float32: far inside every limit.
        assert v["value"] < 1e-5, (k, v)
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def _unchanged(make_step):
    def broken(ops, cfg, inputs):
        step = make_step(ops, cfg, inputs)
        return lambda params: (params, step(params)[1])
    return broken


def _half_batch(make_step):
    """The reference put in the program's place, its mean over half of
    the nodes."""
    def broken(ops, cfg, inputs):
        rows, cols, _ = ops.a.to_coo()
        graph = {"rows": jnp.asarray(rows), "cols": jnp.asarray(cols),
                 "nodes": ops.a.m}
        half = jnp.arange(ops.a.m) < ops.a.m // 2
        return check.reference_step(model_of(cfg), graph, inputs,
                                    cfg["lr"], mask=half)
    return broken


def _altered_loss(make_step):
    def broken(ops, cfg, inputs):
        step = make_step(ops, cfg, inputs)

        def altered(params):
            new, loss = step(params)
            return new, loss * 1.01
        return altered
    return broken


def model_of(cfg):
    import importlib
    return importlib.import_module(f"bench.configs.{cfg['name']}")


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered_loss])
def test_planted_fault_is_not_correct(workload, fault, monkeypatch):
    c = tiny_cell(workload)
    monkeypatch.setattr(c["model"], "make_step",
                        fault(c["model"].make_step))
    res = run(c)
    assert not res["correct"], res["checks"]
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_the_limits(workload):
    c = tiny_cell(workload)
    readings = calibrate.control_readings(c, 11)
    for kind in ("control_bf16", "half_batch", "altered_loss"):
        ok, shown = check.verdict(readings[kind], c["limits"])
        assert not ok, (kind, shown)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("cpu")
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


def test_seed_keys_tell_large_seeds_apart():
    keys = [np.asarray(jax.random.key_data(harness.key_for(s)))
            for s in (1, 2**32 + 1, -1, 2**64 - 1)]
    assert not np.array_equal(keys[0], keys[1])
    assert np.array_equal(keys[2], keys[3])
