"""Multi-head edge values through the hybrid applies: ``(nnz, H)``
values against dense operands whose ``H·c`` columns hold the heads
contiguously, on both backends (the XLA reference and the Pallas
kernels, interpreted here), on the tuned segment tables and on one
block (tile) per segment, against a dense per-head oracle; and
``(nnz, 1)`` against today's ``(nnz,)`` call, bit for bit; and the same
values through ``DistGraphOps`` on an emulated mesh."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecSpec
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.kernels import ref
from repro.kernels.ops import sddmm_apply, spmm_apply
from repro.sparse.generate import mixed_csr

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HEADS = [1, 2, 4]
HEAD_DIMS = [40, 64, 128]


@pytest.fixture(scope="module")
def graph():
    a = mixed_csr(64, 72, seed=3)
    rows, cols, _ = a.to_coo()
    return a, rows, cols


@pytest.fixture(scope="module")
def ops(graph):
    """Both operators of the graph, whose plans split the non-zeros
    between the MXU and the VPU streams, so both kernels of each op run:
    ``ops[False]`` on the tuned segment tables, ``ops[True]`` on one
    block (``ts=1``) and one tile (``cs=ts_tile``) per segment."""
    a = graph[0]
    sp = LibraSpMM(a, spec=ExecSpec(tune="model"))
    sd = LibraSDDMM(a, spec=ExecSpec(tune="model"))
    out = {False: (sp, sd)}
    out[True] = tuple(
        cls(a, spec=ExecSpec(tune=op.tune_config.replace(
            ts=1, cs=op.tune_config.ts_tile)))
        for cls, op in ((LibraSpMM, sp), (LibraSDDMM, sd)))
    for op in out[False] + out[True]:
        assert 0.0 < op.plan.meta["tc_ratio"] < 1.0
    return out


def _spmm(op, ev, b, backend, cfg=None):
    arrs = op.arrays.apply_arrays(revalue=True)
    return np.asarray(spmm_apply(
        ref.revalue_spmm_arrays(arrs, jnp.asarray(ev)), jnp.asarray(b),
        m=op.m, nwin=op.nwin, backend=backend, cfg=cfg or op.tune_config))


def _sddmm(op, x, y, backend, heads):
    return np.asarray(sddmm_apply(
        op.arrays.apply_arrays(), jnp.asarray(x), jnp.asarray(y),
        nnz=op.nnz, backend=backend, cfg=op.tune_config, heads=heads))


def _spmm_oracle(graph, ev, b, heads):
    a, rows, cols = graph
    c = b.shape[1] // heads
    out = []
    for h in range(heads):
        dense = np.zeros((a.m, a.k))
        np.add.at(dense, (rows, cols), ev[:, h])
        out.append(dense @ b[:, h * c:(h + 1) * c].astype(np.float64))
    return np.concatenate(out, axis=1)


def _sddmm_oracle(graph, x, y, heads):
    _, rows, cols = graph
    c = x.shape[1] // heads
    return np.stack([np.einsum("ek,ek->e", x[rows, h * c:(h + 1) * c],
                               y[cols, h * c:(h + 1) * c])
                     for h in range(heads)], axis=1)


@pytest.mark.parametrize("one_unit", [False, True])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("head_dim", HEAD_DIMS)
@pytest.mark.parametrize("heads", HEADS)
def test_multihead_spmm_matches_per_head_oracle(graph, ops, heads, head_dim,
                                                backend, one_unit):
    a = graph[0]
    rng = np.random.default_rng(heads * 1000 + head_dim)
    ev = rng.standard_normal((a.nnz, heads)).astype(np.float32)
    b = rng.standard_normal((a.k, heads * head_dim)).astype(np.float32)
    got = _spmm(ops[one_unit][0], ev, b, backend)
    assert got.shape == (a.m, heads * head_dim)
    np.testing.assert_allclose(got, _spmm_oracle(graph, ev, b, heads),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("one_unit", [False, True])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("head_dim", HEAD_DIMS)
@pytest.mark.parametrize("heads", HEADS)
def test_multihead_sddmm_matches_per_head_oracle(graph, ops, heads, head_dim,
                                                 backend, one_unit):
    a = graph[0]
    rng = np.random.default_rng(heads * 1000 + head_dim + 1)
    x = rng.standard_normal((a.m, heads * head_dim)).astype(np.float32)
    y = rng.standard_normal((a.k, heads * head_dim)).astype(np.float32)
    got = _sddmm(ops[one_unit][1], x, y, backend, heads)
    assert got.shape == (a.nnz, heads)
    np.testing.assert_allclose(got, _sddmm_oracle(graph, x, y, heads),
                               rtol=1e-4, atol=1e-4)


def test_multihead_spmm_block_outer_grid(graph, ops):
    """The lane tile's head map follows the lane axis in either grid
    order (a head of 40 straddles the first two 128-lane tiles)."""
    a = graph[0]
    rng = np.random.default_rng(7)
    ev = rng.standard_normal((a.nnz, 4)).astype(np.float32)
    b = rng.standard_normal((a.k, 160)).astype(np.float32)
    # A cap of 128 gives the 160-wide call two lane tiles: block_outer.
    cfg = ops[False][0].tune_config.replace(nt=128)
    got = _spmm(ops[False][0], ev, b, "pallas", cfg=cfg)
    np.testing.assert_allclose(got, _spmm_oracle(graph, ev, b, 4),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("one_unit", [False, True])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_one_head_is_bitwise_the_single_head_call(graph, ops, backend,
                                                  one_unit):
    a = graph[0]
    sp, sd = ops[one_unit]
    rng = np.random.default_rng(11)
    ev = rng.standard_normal((a.nnz,)).astype(np.float32)
    b = rng.standard_normal((a.k, 40)).astype(np.float32)
    np.testing.assert_array_equal(_spmm(sp, ev[:, None], b, backend),
                                  _spmm(sp, ev, b, backend))
    x = rng.standard_normal((a.m, 40)).astype(np.float32)
    y = rng.standard_normal((a.k, 40)).astype(np.float32)
    one = _sddmm(sd, x, y, backend, 1)
    assert one.shape == (a.nnz, 1)
    np.testing.assert_array_equal(one[:, 0], _sddmm(sd, x, y, backend, None))


def test_dist_graphops_carries_heads_4dev():
    """Multi-head values through DistGraphOps on a 4-way mesh: the SpMM
    and SDDMM gradients with ``(nnz, H)`` values match GraphOps', and a
    UniMP training step matches the single-device one."""
    out = run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.dist import DistGraphOps
        from repro.dist.gnn import make_unimp_train_step
        from repro.models import gnn
        from repro.sparse.generate import mixed_csr
        a = mixed_csr(96, 96, seed=21)
        mesh = jax.make_mesh((4,), ("shards",))
        rng = np.random.default_rng(0)
        g1 = gnn.GraphOps(a)
        gd = DistGraphOps(a, mesh)
        vals = jnp.asarray(rng.standard_normal((a.nnz, 3)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((a.k, 3 * 8)), jnp.float32)
        for g in (g1, gd):
            assert g.spmm(vals, b).shape == (a.m, 24)
        ga = jax.grad(lambda v, b: (g1.spmm(v, b) ** 2).sum(),
                      argnums=(0, 1))(vals, b)
        gb = jax.grad(lambda v, b: (gd.spmm(v, b) ** 2).sum(),
                      argnums=(0, 1))(vals, b)
        for u, w in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        x = jnp.asarray(rng.standard_normal((a.m, 24)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((a.k, 24)), jnp.float32)
        assert gd.sddmm(x, y, heads=3).shape == (a.nnz, 3)
        ga = jax.grad(lambda x, y: (g1.sddmm(x, y, heads=3) ** 2).sum(),
                      argnums=(0, 1))(x, y)
        gb = jax.grad(lambda x, y: (gd.sddmm(x, y, heads=3) ** 2).sum(),
                      argnums=(0, 1))(x, y)
        for u, w in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        feats = jnp.asarray(rng.standard_normal((a.m, 16)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 4, a.m))
        params = gnn.init_unimp(jax.random.PRNGKey(0), [16, 16, 4], 4)
        _, ls = make_unimp_train_step(g1)(params, feats, labels)
        _, ld = make_unimp_train_step(gd)(params, feats, labels)
        assert abs(float(ls) - float(ld)) < 1e-4, (float(ls), float(ld))
        print("DIST_HEADS_OK", float(ls), float(ld))
    """)
    assert "DIST_HEADS_OK" in out


def run_py(code: str, devices: int = 4) -> str:
    """``code`` in a fresh interpreter on ``devices`` emulated CPU
    devices."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout
