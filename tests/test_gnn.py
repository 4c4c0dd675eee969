"""GNN layers on Libra ops: forward vs dense oracle + gradient duality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import gnn
from repro.sparse import power_law_csr
from repro.sparse.generate import mixed_csr


@pytest.fixture(scope="module")
def graph():
    return mixed_csr(96, 96, seed=21)


@pytest.fixture(scope="module")
def gops(graph):
    return gnn.GraphOps(graph)


def test_spmm_forward_matches_dense(graph, gops, rng):
    b = rng.standard_normal((graph.k, 16)).astype(np.float32)
    _, _, vals = graph.to_coo()
    out = np.asarray(gops.spmm(jnp.asarray(vals), jnp.asarray(b)))
    np.testing.assert_allclose(out, graph.to_dense() @ b, rtol=1e-3, atol=1e-3)


def test_spmm_grads_match_dense_autodiff(graph, gops, rng):
    rows, cols, vals = graph.to_coo()
    b = rng.standard_normal((graph.k, 8)).astype(np.float32)

    def libra_loss(v, b):
        return (gops.spmm(v, b) ** 2).sum()

    def dense_loss(v, b):
        dense = jnp.zeros((graph.m, graph.k)).at[rows, cols].set(v)
        return ((dense @ b) ** 2).sum()

    g1 = jax.grad(libra_loss, argnums=(0, 1))(jnp.asarray(vals), jnp.asarray(b))
    g2 = jax.grad(dense_loss, argnums=(0, 1))(jnp.asarray(vals), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-3, atol=1e-2)


def test_sddmm_grads_match_dense_autodiff(graph, gops, rng):
    rows, cols, _ = graph.to_coo()
    x = rng.standard_normal((graph.m, 8)).astype(np.float32)
    y = rng.standard_normal((graph.k, 8)).astype(np.float32)

    def libra_loss(x, y):
        return (gops.sddmm(x, y) ** 2).sum()

    def dense_loss(x, y):
        s = x @ y.T
        return (s[rows, cols] ** 2).sum()

    g1 = jax.grad(libra_loss, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(y))
    g2 = jax.grad(dense_loss, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-3, atol=1e-2)


def test_edge_softmax_rows_sum_to_one(graph, gops, rng):
    scores = jnp.asarray(rng.standard_normal(graph.nnz).astype(np.float32))
    att = gops_att = gnn.edge_softmax(gops, scores)
    sums = jax.ops.segment_sum(att, gops.edge_row, num_segments=graph.m)
    rows_with_edges = np.unique(np.asarray(gops.edge_row))
    np.testing.assert_allclose(np.asarray(sums)[rows_with_edges], 1.0,
                               rtol=1e-5)


def test_gcn_trains_loss_decreases(graph, rng):
    # Standard GCN normalization uses self-loops: Â = D^-½(A+I)D^-½ —
    # they let node features pass through, so planted feature-projection
    # labels are learnable and the loss decrease is guaranteed.
    from repro.sparse.matrix import coo_to_csr

    rows, cols, vals = graph.to_coo()
    eye = np.arange(graph.m, dtype=np.int32)
    a_sl = coo_to_csr(graph.m, graph.k,
                      np.concatenate([rows, eye]),
                      np.concatenate([cols, eye]),
                      np.concatenate([vals, np.ones(graph.m, np.float32)]))
    gops_sl = gnn.GraphOps(a_sl)
    feats = jnp.asarray(rng.standard_normal((graph.m, 16)).astype(np.float32))
    proj = rng.standard_normal((16, 4)).astype(np.float32)
    labels = jnp.asarray(np.argmax(np.asarray(feats) @ proj, axis=1))
    norm = jnp.asarray(gnn.gcn_norm_edges(a_sl))
    params = gnn.init_gcn(jax.random.PRNGKey(0), [16, 16, 4])

    def loss_fn(params):
        logits = gnn.gcn_forward(params, gops_sl, feats, norm)
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()

    vg = jax.jit(jax.value_and_grad(loss_fn))
    loss0 = None
    for step in range(60):
        loss, grads = vg(params)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
        if loss0 is None:
            loss0 = float(loss)
    assert float(loss) < loss0 * 0.9, (loss0, float(loss))


def test_agnn_forward_finite(graph, gops, rng):
    feats = jnp.asarray(rng.standard_normal((graph.m, 12)).astype(np.float32))
    params = gnn.init_agnn(jax.random.PRNGKey(1), [12, 8])
    out = gnn.agnn_forward(params, gops, feats)
    assert out.shape == (graph.m, 8)
    assert bool(jnp.isfinite(out).all())


def test_transpose_perm_roundtrip():
    a = power_law_csr(48, 40, 4.0, seed=5)
    at, perm = gnn.transpose_csr(a)
    rows, cols, vals = a.to_coo()
    rt, ct, vt = at.to_coo()
    np.testing.assert_array_equal(rt, cols[perm])
    np.testing.assert_array_equal(ct, rows[perm])
    np.testing.assert_allclose(vt, vals[perm])


def _scope_names(op_name: str) -> list[str]:
    """``jit(f)/transpose(jvp(spmm))/vpu`` → ``["f", "spmm", "vpu"]``."""
    out = []
    for part in op_name.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part:
            out.append(part)
    return out


def test_training_steps_scope_each_sparse_operator(graph):
    """Every op of the jitted steps falls under at most one of the
    operator scopes, and each operator the step runs has its scope."""
    import re

    from repro.dist.gnn import (make_agnn_train_step, make_gcn_train_step,
                                make_unimp_train_step)

    g = gnn.GraphOps(graph)
    x = jnp.ones((graph.m, 8), jnp.float32)
    labels = jnp.zeros((graph.m,), jnp.int32)
    ev = jnp.ones((graph.nnz,), jnp.float32)
    gcn = [{"w": jnp.ones((8, 4))}]
    agnn = [{"w": jnp.ones((8, 4)), "beta": jnp.ones(())}]
    texts = {
        "gcn_train_step": make_gcn_train_step(g).lower(
            gcn, x, labels, ev).compile().as_text(),
        "agnn_train_step": make_agnn_train_step(g).lower(
            agnn, x, labels).compile().as_text(),
        "unimp_train_step": make_unimp_train_step(g).lower(
            gnn.init_unimp(jax.random.PRNGKey(0), [8, 8, 4], 2), x,
            labels).compile().as_text(),
    }
    operators = {"spmm", "sddmm", "edge_softmax"}
    dense = {"qkv", "gate"}
    for name, text in texts.items():
        seen = set()
        for op_name in re.findall(r'op_name="([^"]*)"', text):
            parts = _scope_names(op_name)
            inside = (operators | dense) & set(parts)
            assert len(inside) <= 1, op_name
            seen |= inside
        assert f"jit({name})" in text
        assert seen == ({"spmm"} if name == "gcn_train_step" else operators
                        | (dense if name == "unimp_train_step" else set()))


# ---------------------------------------------------------------- UniMP ---
@pytest.fixture(scope="module")
def looped():
    """A small power-law graph with a self loop on every node, as the
    benchmark's graphs have."""
    a = power_law_csr(64, 64, 4.0, seed=9)
    rows, cols, _ = a.to_coo()
    keep = rows != cols
    n = np.arange(a.m)
    from repro.sparse.matrix import coo_to_csr

    r = np.concatenate([rows[keep], n])
    c = np.concatenate([cols[keep], n])
    return coo_to_csr(a.m, a.k, r, c, np.ones(r.size, np.float32))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_unimp_matches_the_plain_reference(looped, backend):
    """Logits and every parameter's gradient of ``unimp_forward`` through
    ``GraphOps`` against ``bench/configs/unimp.py``'s COO reference, on
    seeded random weights, at ``highest`` precision. The key bias's true
    gradient is 0 (a shift of every key of a row shifts its scores
    alike), so each leaf is held to the largest gradient's scale."""
    from bench.configs import unimp

    from repro.api import ExecSpec

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((looped.m, 12)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 5, looped.m))
    params = gnn.init_unimp(jax.random.PRNGKey(4), [12, 16, 16, 5], 4)
    rows, cols, _ = looped.to_coo()
    graph = {"rows": jnp.asarray(rows), "cols": jnp.asarray(cols),
             "nodes": looped.m}
    g = gnn.GraphOps(looped, spec=ExecSpec(backend=backend, tune="model"))

    def nll(logits):
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()

    def program(p):
        return gnn.unimp_forward(p, g, x)

    def reference(p):
        return unimp.reference_logits(p, graph, {"feats": x})

    with jax.default_matmul_precision("highest"):
        got, want = program(params), reference(params)
        g_got = jax.grad(lambda p: nll(program(p)))(params)
        g_want = jax.grad(lambda p: nll(reference(p)))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    scale = max(float(jnp.abs(v).max()) for v in jax.tree.leaves(g_want))
    for u, v in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-4,
                                   atol=1e-5 * scale)


def test_unimp_head_counters_on_an_enabled_tracer(looped):
    """Each multi-head call site of a traced step opens a
    ``graphops.heads`` span with its layout; a disabled tracer records
    nothing."""
    from repro.dist.gnn import make_unimp_train_step
    from repro.obs.trace import Tracer, use_tracer

    g = gnn.GraphOps(looped)
    x = jnp.ones((looped.m, 8), jnp.float32)
    labels = jnp.zeros((looped.m,), jnp.int32)
    params = gnn.init_unimp(jax.random.PRNGKey(0), [8, 256, 40], 4)
    tr = Tracer()
    with use_tracer(tr):
        make_unimp_train_step(g).lower(params, x, labels)
    spans = [s.attrs for s in tr.roots if s.name == "graphops.heads"]
    # Per layer: forward SDDMM and SpMM; backward SpMM (into v), SDDMM
    # (dα), and the SDDMM's two SpMMs (into q and k).
    assert len(spans) == 2 * 6
    assert sorted(s["op"] for s in spans) == ["sddmm"] * 4 + ["spmm"] * 8
    widths = {(s["heads"], s["head_dim"], s["head_stride"], s["lane_fill"])
              for s in spans}
    assert widths == {(4, 64, 64, 100.0), (4, 40, 40, 62.5)}
    quiet = Tracer(enabled=False)
    with use_tracer(quiet):
        make_unimp_train_step(g).lower(params, x, labels)
    assert not quiet.roots


def _spans_named(roots, name):
    out = []
    for sp in roots:
        if sp.name == name:
            out.append(sp.attrs)
        out += _spans_named(sp.children, name)
    return out


@pytest.mark.parametrize("model", ["gcn", "unimp"])
def test_every_sparse_call_fetches_each_row_once_up_to_256(looped, model):
    """Each SpMM and SDDMM call site of a traced step opens a
    ``graphops.tile`` span with its width, lane tile and lane tiles; at
    widths up to 256 under the tuned cap every call takes one tile, so
    each dense row is copied once per call. A multi-head call's
    ``graphops.heads`` span reads the same tile."""
    from repro.dist.gnn import make_gcn_train_step, make_unimp_train_step
    from repro.obs.trace import Tracer, use_tracer

    from repro.api import ExecSpec

    g = gnn.GraphOps(looped, spec=ExecSpec(tune="model"))
    x = jnp.ones((looped.m, 128), jnp.float32)
    labels = jnp.zeros((looped.m,), jnp.int32)
    tr = Tracer()
    with use_tracer(tr):
        if model == "gcn":
            params = gnn.init_gcn(jax.random.PRNGKey(0), [128, 256, 256, 40])
            make_gcn_train_step(g).lower(
                params, x, labels, jnp.ones((looped.nnz,), jnp.float32))
        else:
            params = gnn.init_unimp(jax.random.PRNGKey(0), [128, 256, 40], 4)
            make_unimp_train_step(g).lower(params, x, labels)
    tiles = _spans_named(tr.roots, "graphops.tile")
    # GCN, a layer: the forward SpMM and, in backward, the SpMM into
    # its input and the SDDMM into the edge values (traced, though the
    # step drops it); UniMP: the 12 call sites of
    # test_unimp_head_counters_on_an_enabled_tracer.
    assert len(tiles) == (9 if model == "gcn" else 12)
    widths = {(t["op"], t["width"], t["tile"], t["lane_tiles"])
              for t in tiles}
    if model == "gcn":
        assert widths == {(op, w, t, 1) for op in ("spmm", "sddmm")
                          for w, t in ((256, 256), (40, 128))}
    else:
        assert widths == {(op, w, 256, 1) for op in ("spmm", "sddmm")
                          for w in (256, 160)}
        heads = _spans_named(tr.roots, "graphops.heads")
        assert {h["lane_fill"] for h in heads} == {100.0, 62.5}
