"""Exphormer on Libra plans: the model against the plain reference of
``bench/configs/exphormer.py``, the softmax across several plans, the
expander, and the spans and scopes of the typed attention."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecSpec
from repro.models import gnn
from repro.sparse import power_law_csr
from repro.sparse.matrix import coo_to_csr

N = 64
DIMS = [12, 16, 16, 5]


@pytest.fixture(scope="module")
def looped():
    """A small power-law graph with a self loop on every node, as the
    benchmark's graphs have."""
    a = power_law_csr(N, N, 4.0, seed=9)
    rows, cols, _ = a.to_coo()
    keep = rows != cols
    r = np.concatenate([rows[keep], np.arange(N)])
    c = np.concatenate([cols[keep], np.arange(N)])
    return coo_to_csr(N, N, r, c, np.ones(r.size, np.float32))


@pytest.fixture(scope="module")
def expander():
    from bench.configs import exphormer

    return gnn.expander_csr(N, exphormer.PERMS, exphormer.EXPANDER_SEED)


def _inputs(a):
    rng = np.random.default_rng(3)
    return {"feats": jnp.asarray(rng.standard_normal((a.m, DIMS[0])),
                                 jnp.float32),
            "labels": jnp.asarray(rng.integers(0, DIMS[-1], a.m), jnp.int32),
            "edges": jnp.asarray(gnn.gcn_norm_edges(a))}


def _close(got, want, rtol=1e-4, atol_scale=1e-5):
    scale = max(float(jnp.abs(v).max()) for v in jax.tree.leaves(want))
    for u, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=rtol,
                                   atol=atol_scale * scale)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_exphormer_matches_the_plain_reference(looped, expander, backend):
    """Logits, loss, the first gradient and three SGD steps of
    ``exphormer_forward`` through two ``GraphOps`` against the COO
    reference over ``n + 1`` nodes, on seeded random weights, at
    ``highest`` precision. Biases that a BatchNorm follows have a true
    gradient of 0, so each leaf is held to the largest leaf's scale."""
    from bench import check
    from bench.configs import exphormer

    from repro.dist.gnn import make_exphormer_train_step

    spec = ExecSpec(backend=backend, tune="model")
    g, gx = gnn.GraphOps(looped, spec=spec), gnn.GraphOps(expander,
                                                          spec=spec)
    inputs = _inputs(looped)
    rows, cols, _ = looped.to_coo()
    graph = {"rows": jnp.asarray(rows), "cols": jnp.asarray(cols),
             "nodes": N}
    params = gnn.init_exphormer(jax.random.PRNGKey(4), DIMS,
                                exphormer.HEADS)
    lr = 0.2
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: gnn.exphormer_forward(
            p, g, gx, inputs["feats"], inputs["edges"]))(params)
        want = exphormer.reference_logits(params, graph, inputs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        step = make_exphormer_train_step(g, gx, lr=lr)
        ref_step = check.reference_step(exphormer, graph, inputs, lr)
        p, r = params, params
        for i in range(3):
            p, loss = step(p, inputs["feats"], inputs["labels"],
                           inputs["edges"])
            r, ref_loss = ref_step(r)
            np.testing.assert_allclose(float(loss), float(ref_loss),
                                       rtol=1e-5)
            if i == 0:
                grads = jax.tree.map(lambda a, b: (a - b) / lr, params, p)
                ref_grads = jax.tree.map(lambda a, b: (a - b) / lr, params,
                                         r)
                _close(grads, ref_grads)
        _close(p, r)


def _old_edge_softmax(g, scores):
    """``edge_softmax`` as it was before it took several parts."""
    with jax.named_scope("edge_softmax"):
        mx = jax.ops.segment_max(scores, g.edge_row, num_segments=g.m)
        e = jnp.exp(scores - mx[g.edge_row])
        z = jax.ops.segment_sum(e, g.edge_row, num_segments=g.m)
        return e / jnp.maximum(z[g.edge_row], 1e-9)


@pytest.mark.parametrize("heads", [None, 4])
def test_one_part_edge_softmax_is_unchanged(looped, heads):
    """The one-part call gives the old function's values and gradients
    bit for bit, eagerly and under ``jit``."""
    g = gnn.GraphOps(looped)
    shape = (looped.nnz,) if heads is None else (looped.nnz, heads)
    s = jnp.asarray(np.random.default_rng(1).standard_normal(shape),
                    jnp.float32) * 4
    w = jnp.asarray(np.random.default_rng(2).standard_normal(shape),
                    jnp.float32)
    for fn in (lambda f: f, jax.jit):
        new = fn(lambda x: gnn.edge_softmax(g, x))(s)
        old = fn(lambda x: _old_edge_softmax(g, x))(s)
        assert np.array_equal(np.asarray(new), np.asarray(old))
        d_new = fn(jax.grad(lambda x: (gnn.edge_softmax(g, x) * w).sum()))(s)
        d_old = fn(jax.grad(lambda x: (_old_edge_softmax(g, x) * w).sum()))(s)
        assert np.array_equal(np.asarray(d_new), np.asarray(d_old))


@pytest.mark.parametrize("heads", [None, 4])
def test_typed_edge_softmax_is_one_softmax_over_the_typed_lists(
        looped, expander, heads):
    """Two parts and a dense term equal one softmax over the
    concatenated COO lists of every type, a pair that is an edge of both
    parts counting twice; so do their gradients."""
    rows_x, cols_x, _ = expander.to_coo()
    # The expander plus pairs that are also edges of ``looped``.
    rows_g, cols_g, _ = looped.to_coo()
    both = np.flatnonzero(rows_g % 3 == 0)
    keys = np.union1d(rows_x.astype(np.int64) * N + cols_x,
                      rows_g[both].astype(np.int64) * N + cols_g[both])
    other = coo_to_csr(N, N, keys // N, keys % N,
                       np.ones(keys.size, np.float32))
    assert np.intersect1d(keys, rows_g.astype(np.int64) * N
                          + cols_g).size >= both.size
    g, gx = gnn.GraphOps(looped), gnn.GraphOps(other)
    rng = np.random.default_rng(5)

    def draw(k):
        shape = (k,) if heads is None else (k, heads)
        return jnp.asarray(rng.standard_normal(shape) * 3, jnp.float32)

    s_g, s_x, s_d = draw(g.nnz), draw(gx.nnz), draw(N)
    rows_o, _, _ = other.to_coo()
    rows = np.concatenate([rows_g, rows_o, np.arange(N)])

    def one(s_g, s_x, s_d):
        s = jnp.concatenate([s_g, s_x, s_d])
        top = jax.ops.segment_max(s, rows, num_segments=N)
        e = jnp.exp(s - top[rows])
        att = e / jax.ops.segment_sum(e, rows, num_segments=N)[rows]
        return att[:g.nnz], att[g.nnz:g.nnz + gx.nnz], att[g.nnz + gx.nnz:]

    def typed(s_g, s_x, s_d):
        return gnn.edge_softmax(g, s_g, more=[(gx, s_x)], dense=s_d)

    got, want = typed(s_g, s_x, s_d), one(s_g, s_x, s_d)
    assert len(got) == 3
    for u, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-5,
                                   atol=1e-7)
    weights = [draw(x.shape[0]) for x in want]

    def loss(f):
        return lambda *s: sum((a * w).sum() for a, w in zip(f(*s), weights))

    d_got = jax.grad(loss(typed), argnums=(0, 1, 2))(s_g, s_x, s_d)
    d_want = jax.grad(loss(one), argnums=(0, 1, 2))(s_g, s_x, s_d)
    for u, v in zip(d_got, d_want):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-4,
                                   atol=1e-6)


def test_typed_edge_softmax_needs_one_row_count(looped):
    g = gnn.GraphOps(looped)
    small = gnn.GraphOps(gnn.expander_csr(N // 2, 2, 0))
    with pytest.raises(ValueError, match="row counts"):
        gnn.edge_softmax(g, jnp.zeros(g.nnz), more=[(small,
                                                     jnp.zeros(small.nnz))])


@pytest.mark.parametrize("n,perms", [(N, 3), (1000, 3), (7, 4)])
def test_expander_is_symmetric_simple_and_bounded(n, perms):
    a = gnn.expander_csr(n, perms, seed=11)
    rows, cols, vals = a.to_coo()
    keys = rows.astype(np.int64) * n + cols
    assert np.all(rows != cols)
    assert np.unique(keys).size == keys.size
    assert np.array_equal(np.sort(keys), np.sort(cols.astype(np.int64) * n
                                                 + rows))
    assert np.diff(a.indptr).max() <= 2 * perms
    assert np.all(vals == 1)


def test_expander_is_the_same_for_the_same_seed():
    a, b = gnn.expander_csr(500, 3, 7), gnn.expander_csr(500, 3, 7)
    c = gnn.expander_csr(500, 3, 8)
    assert np.array_equal(a.indptr, b.indptr)
    assert np.array_equal(a.indices, b.indices)
    assert not (np.array_equal(a.indptr, c.indptr)
                and np.array_equal(a.indices, c.indices))


def _spans_named(roots, name):
    out = []
    for sp in roots:
        if sp.name == name:
            out.append(sp.attrs)
        out += _spans_named(sp.children, name)
    return out


def test_exphormer_counters_and_scopes(looped):
    """Under an enabled tracer the expander's draw is one
    ``exphormer.expander`` span, its plans one ``graphops.build``, and
    each typed attention of a traced step one ``graphops.types`` span;
    a disabled tracer records nothing. The compiled step names itself
    and its scopes, and every op lies under at most one operator scope."""
    from bench.scopes import in_scope, scope_path

    from repro.dist.gnn import make_exphormer_train_step
    from repro.obs.trace import Tracer, use_tracer

    tr = Tracer()
    with use_tracer(tr):
        x = gnn.expander_csr(N, 3, 2)
        gx = gnn.GraphOps(x)
    (draw,) = _spans_named(tr.roots, "exphormer.expander")
    assert draw == {"nodes": N, "perms": 3, "nnz": x.nnz,
                    "dropped": 2 * 3 * N - x.nnz}
    assert [s.attrs["nnz"] for s in tr.roots
            if s.name == "graphops.build"] == [x.nnz]

    g = gnn.GraphOps(looped)
    params = gnn.init_exphormer(jax.random.PRNGKey(0), DIMS, 4)
    args = (params, jnp.ones((N, DIMS[0]), jnp.float32),
            jnp.zeros((N,), jnp.int32), jnp.asarray(gnn.gcn_norm_edges(
                looped)))
    tr = Tracer()
    with use_tracer(tr):
        lowered = make_exphormer_train_step(g, gx).lower(*args)
    types = _spans_named(tr.roots, "graphops.types")
    assert types == [{"types": 3, "nnz_graph": g.nnz, "nnz_expander": x.nnz,
                      "dense_edges": 2 * N + 1}] * (len(DIMS) - 1)
    quiet = Tracer(enabled=False)
    with use_tracer(quiet):
        gnn.expander_csr(N, 3, 2)
        make_exphormer_train_step(g, gx).lower(*args)
    assert not quiet.roots

    text = lowered.compile().as_text()
    assert "jit(exphormer_train_step)" in text
    paths = [scope_path(n) for n in re.findall(r'op_name="([^"]*)"', text)]
    operators = {"spmm", "sddmm", "edge_softmax"}
    for path in paths:
        assert len(operators & set(path)) <= 1, path
    for scope in ("spmm", "sddmm", "gps/local", "attn/graph",
                  "attn/expander", "attn/virtual",
                  "edge_softmax/typed_softmax", "gps/ffn"):
        assert any(in_scope(p, scope) for p in paths), scope
