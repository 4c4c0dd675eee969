"""Single-pass fused hybrid apply: Pallas (interpret) vs the XLA oracle.

Covers the per-block TC layout, the id-driven B row fetch, and the fused
scatter-accumulate epilogue across modes, awkward (non-multiple-of-tile)
shapes, empty-TC / empty-VPU plans, and large-k matrices.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import WINDOW
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.core.windows import num_windows
from repro.kernels import ref
from repro.sparse.generate import (
    banded_csr,
    mixed_csr,
    power_law_csr,
    random_uniform_csr,
)


def _rand(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def _check_spmm(rng, a, mode, n, **kw):
    b = _rand(rng, a.k, n)
    oracle = ref.spmm_dense_oracle(a.to_dense(), b)
    op = LibraSpMM(a, mode=mode, **kw)
    out_x = np.asarray(op(jnp.asarray(b), backend="xla"))
    out_p = np.asarray(op(jnp.asarray(b), backend="pallas"))
    np.testing.assert_allclose(out_x, oracle, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(out_p, oracle, rtol=1e-3, atol=1e-3)
    return op


@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
@pytest.mark.parametrize("m,k,n", [
    (80, 64, 48),     # m not multiple of 8, n not multiple of nt
    (61, 93, 37),     # nothing aligned
    (96, 96, 128),    # fully aligned
])
def test_fused_spmm_modes_and_ragged_shapes(rng, mode, m, k, n):
    a = mixed_csr(m, k, seed=m + k)
    _check_spmm(rng, a, mode, n)


def test_fused_spmm_empty_tc_plan(rng):
    """Uniform hyper-sparse ⇒ no vector passes the threshold: the TC side
    is the dummy zero block and must contribute nothing."""
    a = random_uniform_csr(64, 64, 0.004, seed=5)
    op = _check_spmm(rng, a, "hybrid", 32)
    assert not op.plan.meta["has_tc"]
    assert op.plan.tc.n_active == 1  # dummy block only


def test_fused_spmm_empty_vpu_plan(rng):
    """Dense band ⇒ every vector passes in tcu mode: the VPU side is the
    dummy zero tile and must contribute nothing."""
    a = banded_csr(64, 64, 8, 1.0, seed=6)
    op = _check_spmm(rng, a, "tcu", 32)
    assert op.plan.meta["tc_ratio"] == 1.0
    assert op.plan.vpu.nnz == 0


def test_tc_window_compaction_map(rng):
    """rank/active_win invariants + the compacted output really is smaller
    than the dense (nwin, 8, n) layout on a scattered-TC matrix."""
    a = power_law_csr(256, 128, 9.0, seed=7)
    op = LibraSpMM(a, mode="hybrid")
    tc = op.plan.tc
    nwin = num_windows(a.m)
    assert np.array_equal(tc.active_win[tc.rank], tc.window)
    assert np.all(np.diff(tc.rank) >= 0)  # blocks stay window-sorted
    assert tc.n_active <= nwin
    if op.plan.meta["has_tc"]:
        assert tc.n_active == len(np.unique(tc.window))
    # the device-side scatter map covers exactly active_win
    rows = np.asarray(op.arrays["tc_seg_row"]).reshape(-1, WINDOW)
    assert np.array_equal(np.unique(rows[:, 0] // WINDOW), tc.active_win)


@pytest.mark.parametrize("k", [4608, 16384])
def test_fused_spmm_large_k_tiled(rng, k):
    """Large k: the Pallas path fetches only the B rows the plan names
    (no k-panel sweep, never whole-k resident) and must match the
    oracle."""
    a = random_uniform_csr(32, k, 40.0 / k, seed=k)
    b = _rand(rng, k, 128)
    oracle = ref.spmm_dense_oracle(a.to_dense(), b)
    op = LibraSpMM(a)
    out = np.asarray(op(jnp.asarray(b), backend="pallas"))
    np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
def test_fused_sddmm_modes_ragged_kf(rng, mode):
    a = mixed_csr(72, 56, seed=9)  # m, k not tile multiples
    x = _rand(rng, a.m, 40)        # kf not a multiple of the feature tile
    y = _rand(rng, a.k, 40)
    oracle = ref.sddmm_dense_oracle(a.to_dense(), x, y)
    op = LibraSDDMM(a, mode=mode)
    for backend in ("xla", "pallas"):
        out = np.asarray(op(jnp.asarray(x), jnp.asarray(y), backend=backend))
        np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3)


def test_apply_cache_reuse(rng):
    """Repeated calls with the same (n, dtype, backend) reuse one jitted
    closure; a new n or backend adds a new entry."""
    a = mixed_csr(64, 64, seed=10)
    op = LibraSpMM(a)
    b1 = jnp.asarray(_rand(rng, a.k, 32))
    out1 = op(b1)
    assert len(op._apply_cache) == 1
    fn = next(iter(op._apply_cache.values()))
    out1b = op(b1)
    assert next(iter(op._apply_cache.values())) is fn
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out1b))
    op(jnp.asarray(_rand(rng, a.k, 16)))
    assert len(op._apply_cache) == 2
    op(b1, backend="pallas")
    assert len(op._apply_cache) == 3
