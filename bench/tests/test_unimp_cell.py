"""A whole run of ``unimp.arxiv`` at a tiny size on the CPU (Pallas
interpreter, four heads of four features): the program against the
float32 reference, the planted faults that ``correct`` has to catch,
and the control. The helpers are ``test_harness.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, check, counts
from bench.configs import unimp
from bench.tests.test_harness import (_altered_loss, _half_batch, _unchanged,
                                      run, tiny_cell)

WORKLOAD = "unimp.arxiv"


def test_program_matches_reference():
    res = run(tiny_cell(WORKLOAD))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for k, v in res["checks"].items():
        # Interpreted kernels in float32: far inside every limit.
        assert v["value"] < 1e-5, (k, v)
    assert set(res["metrics"]) == {"step_s", "setup_s"}


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered_loss])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    c = tiny_cell(WORKLOAD)
    monkeypatch.setattr(c["model"], "make_step",
                        fault(c["model"].make_step))
    res = run(c)
    assert not res["correct"], res["checks"]
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


def test_control_fails_the_limits():
    c = tiny_cell(WORKLOAD)
    readings = calibrate.control_readings(c, 11)
    for kind in ("control_bf16", "half_batch", "altered_loss"):
        ok, shown = check.verdict(readings[kind], c["limits"])
        assert not ok, (kind, shown)


def test_control_reference_runs_in_bfloat16():
    """The control casts the parameters and the inputs to bfloat16; the
    reference's products, gathers, softmax, segment sums and gate then
    compute in bfloat16 (no operand promotes them to float32; a
    reduction may accumulate in float32, as ``jnp`` does, and round back)."""
    dims = [16, 32, 32, 5]
    params = unimp.init_params(jax.random.PRNGKey(0), dims)
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(6), 3).astype(np.int32)
    graph = {"rows": jnp.asarray(rows),
             "cols": jnp.asarray(rng.integers(0, 6, rows.size, np.int32)),
             "nodes": 6}
    inputs = {"feats": jnp.asarray(rng.normal(size=(6, 16)), jnp.bfloat16)}
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    logits = unimp.reference_logits(bf16, graph, inputs)
    assert logits.dtype == jnp.bfloat16
    jaxpr = jax.make_jaxpr(
        lambda p, x: unimp.reference_logits(p, graph, x))(bf16, inputs)

    layer_ops = {"dot_general", "gather", "exp", "scatter-max", "scatter-add",
                 "logistic", "concatenate"}

    def dtypes(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in layer_ops:
                yield from (v.aval.dtype for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dtypes(sub)

    assert set(dtypes(jaxpr.jaxpr)) == {jnp.dtype(jnp.bfloat16)}


def test_step_counts_follow_the_layer_equations():
    """18 sparse calls a step at widths 256, 256 and 160 at arxiv's
    sizes; their FLOPs are part of the step's, and at one head the bytes
    are ``bench/scopes.py``'s."""
    from bench import scopes

    dims = [128, 256, 256, 40]
    calls = unimp.sparse_calls(169_343, 2_501_785, dims)
    assert len(calls) == 18
    assert sorted({c[4] for c in calls}) == [160, 256]
    assert sum(c[0] == "sddmm" for c in calls) == 6
    sparse = sum(unimp.call_flops(*c) for c in calls)
    assert sparse == sum(counts.spmm(c[1], c[4]) for c in calls)
    assert sparse < unimp.step_flops(169_343, 2_501_785, dims)
    for op in ("spmm", "sddmm"):
        assert unimp.call_bytes(op, 100, 10, 12, 64, 1) == \
            scopes.call_bytes(op, 100, 10, 12, 64)
        assert unimp.call_bytes(op, 100, 10, 12, 64, 4) == \
            scopes.call_bytes(op, 100, 10, 12, 64) + 4 * 300


def test_parameters_follow_the_head_layout():
    params = unimp.init_params(jax.random.PRNGKey(0), [16, 32, 32, 5])
    heads = unimp.HEADS
    assert [lp["q_w"].shape for lp in params] == [
        (16, heads, 8), (32, heads, 8), (32, heads, 5)]
    assert "ln_g" in params[0] and "ln_g" not in params[-1]
    assert params[-1]["r_w"].shape == (32, 5)
