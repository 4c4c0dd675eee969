"""A whole run of ``exphormer.arxiv`` at a tiny size on the CPU (Pallas
interpreter, four heads of four features): the program against the
float32 reference, the planted faults that ``correct`` has to catch,
and the control. The helpers are ``test_harness.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import calibrate, check, counts
from bench.configs import exphormer
from bench.tests.test_harness import _half_batch, run, tiny_cell

WORKLOAD = "exphormer.arxiv"


def test_program_matches_reference():
    res = run(tiny_cell(WORKLOAD))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for k, v in res["checks"].items():
        # Interpreted kernels in float32: far inside every limit.
        assert v["value"] < 1e-5, (k, v)
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def test_planted_fault_is_not_correct(monkeypatch):
    """The reference's mean over half of the nodes, put in the program's
    place, fails a limit of a whole run."""
    c = tiny_cell(WORKLOAD)
    monkeypatch.setattr(c["model"], "make_step",
                        _half_batch(c["model"].make_step))
    res = run(c)
    assert not res["correct"], res["checks"]
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


def test_control_and_faults_fail_the_limits():
    """The bfloat16 control, ``half_batch`` and ``altered_loss`` each
    fail at least one limit."""
    c = tiny_cell(WORKLOAD)
    readings = calibrate.control_readings(c, 11)
    for kind in ("control_bf16", "half_batch", "altered_loss"):
        ok, shown = check.verdict(readings[kind], c["limits"])
        assert not ok, (kind, shown)


def test_control_reference_runs_in_bfloat16():
    """The control casts the parameters and the inputs to bfloat16; the
    reference's products, gathers, scores, softmax, segment sums and
    BatchNorms then compute in bfloat16 (no operand promotes them to
    float32; a reduction may accumulate in float32, as ``jnp`` does, and
    round back: the mean's division by its count is left out)."""
    dims = [16, 32, 32, 5]
    params = exphormer.init_params(jax.random.PRNGKey(0), dims)
    rng = np.random.default_rng(0)
    n = 24
    rows = np.repeat(np.arange(n), 3).astype(np.int32)
    graph = {"rows": jnp.asarray(rows),
             "cols": jnp.asarray(rng.integers(0, n, rows.size, np.int32)),
             "nodes": n}
    inputs = {"feats": jnp.asarray(rng.normal(size=(n, 16)), jnp.bfloat16),
              "edges": jnp.asarray(rng.random(rows.size), jnp.bfloat16)}
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    logits = exphormer.reference_logits(bf16, graph, inputs)
    assert logits.dtype == jnp.bfloat16
    jaxpr = jax.make_jaxpr(
        lambda p, x: exphormer.reference_logits(p, graph, x))(bf16, inputs)

    layer_ops = {"dot_general", "gather", "mul", "exp", "clamp",
                 "scatter-max", "scatter-add", "rsqrt", "sqrt", "div",
                 "max", "concatenate"}
    seen = set()

    def dtypes(jx):
        for eqn in jx.eqns:
            count = (eqn.primitive.name == "div"
                     and eqn.invars[1].aval.shape == ())
            if eqn.primitive.name in layer_ops and not count:
                seen.add(eqn.primitive.name)
                yield from (v.aval.dtype for v in eqn.outvars
                            if jnp.issubdtype(v.aval.dtype, jnp.floating))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dtypes(sub)

    assert set(dtypes(jaxpr.jaxpr)) == {jnp.dtype(jnp.bfloat16)}
    assert {"gather", "exp", "scatter-max", "scatter-add",
            "dot_general"} <= seen


def test_parameters_follow_the_configuration():
    """Heads of ``w / HEADS`` features, a key scaling per edge type and
    head, the FFN's hidden width, one block per ``layers``."""
    cfg = tiny_cell(WORKLOAD)["cfg"]
    full = {**cfg, "hidden": 256}
    dims = exphormer.dims(full, {"features": 128, "classes": 40})
    assert dims == [128, 256, 256, 40]
    params = jax.eval_shape(lambda: exphormer.init_params(
        jax.random.PRNGKey(0), dims))
    heads = exphormer.HEADS
    assert heads == cfg["heads"] == 4
    assert len(params["layers"]) == cfg["layers"] == 3
    for lp in params["layers"]:
        for name in ("q", "k", "v"):
            assert lp[f"{name}_w"].shape == (256, heads, 256 // heads)
        for t in exphormer.TYPES:
            assert lp[f"e_{t}"].shape == (heads, 256 // heads)
        assert lp["f1_w"].shape == (256, cfg["ffn_hidden"])
    assert params["in_w"].shape == (128, 256)
    assert params["out_w"].shape == (256, 40)
    assert params["virtual"].shape == (256,)


def test_step_counts_follow_the_layer_equations():
    """Per block: the GCN local's two SpMMs and six four-head sparse
    calls on each of the two plans; their FLOPs are part of the step's,
    and the bytes are UniMP's per call."""
    nodes, edges = 1000, 6000
    dims = [128, 256, 256, 40]
    calls = exphormer.sparse_calls(nodes, edges, dims)
    assert len(calls) == 3 * 14
    assert sum(c[0] == "sddmm" for c in calls) == 3 * 4
    assert {c[1] for c in calls} == {edges, exphormer.expander_nnz(nodes)}
    sparse = sum(exphormer.call_flops(*c) for c in calls)
    assert sparse == sum(counts.spmm(c[1], c[4]) for c in calls)
    assert sparse < exphormer.step_flops(nodes, edges, dims)
