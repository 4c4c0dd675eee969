"""Per-kernel interpret-mode validation against the pure-jnp oracles:
shape/dtype sweeps + end-to-end hybrid op vs dense oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import preprocess
from repro.core.formats import WINDOW, device_arrays
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.core.windows import num_windows
from repro.kernels import ref
from repro.kernels.ops import sddmm_apply, spmm_apply
from repro.kernels.sddmm_mxu import sddmm_mxu
from repro.kernels.sddmm_vpu import sddmm_vpu
from repro.kernels.spmm_mxu import spmm_mxu
from repro.kernels.spmm_vpu import spmm_vpu
from repro.sparse import banded_csr, power_law_csr, random_uniform_csr
from repro.sparse.generate import mixed_csr


def _rand(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


# ``col_hi`` narrows the column ids to [0, col_hi) so many ids repeat
# (None: all of k) — the row fetch must copy a shared row once per id.
@pytest.mark.parametrize("nb,bk,k,n,nt,col_hi", [
    (1, 8, 32, 128, 128, None),
    (5, 16, 64, 128, 64, 32),
    (9, 32, 128, 256, 128, 32),
])
def test_spmm_mxu_matches_compact_ref(rng, nb, bk, k, n, nt, col_hi):
    nwin = 4
    window = np.sort(rng.integers(0, nwin, nb)).astype(np.int32)
    active = np.unique(window)
    rank = np.searchsorted(active, window).astype(np.int32)
    cols = rng.integers(0, col_hi or k, (nb, bk)).astype(np.int32)
    vals = _rand(rng, nb, WINDOW, bk)
    b = _rand(rng, k, n)
    per_block = spmm_mxu(jnp.asarray(vals), jnp.asarray(cols),
                         jnp.asarray(b), nt=nt, interpret=True)
    out = jax.ops.segment_sum(per_block.reshape(nb, WINDOW, n),
                              jnp.asarray(rank), num_segments=active.size)
    out = out.reshape(active.size * WINDOW, n)
    expect = ref.spmm_tc_compact_ref(jnp.asarray(vals), jnp.asarray(cols),
                                     jnp.asarray(rank), jnp.asarray(b),
                                     active.size)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ntiles,ts,k,n,col_hi", [
    (1, 8, 16, 128, None),
    (7, 32, 64, 128, 16),
])
def test_spmm_vpu_matches_ref(rng, ntiles, ts, k, n, col_hi):
    vals = _rand(rng, ntiles, ts)
    cols = rng.integers(0, col_hi or k, (ntiles, ts)).astype(np.int32)
    b = _rand(rng, k, n)
    out = spmm_vpu(jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(b),
                   nt=128, interpret=True)
    gathered = b[cols]
    expect = np.einsum("tj,tjn->tn", vals, gathered)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nb,bk,kf", [(3, 16, 128), (6, 16, 256), (2, 8, 128)])
def test_sddmm_mxu_matches_ref(rng, nb, bk, kf):
    nwin = 3
    ncols = 64
    window = np.sort(rng.integers(0, nwin, nb)).astype(np.int32)
    cols = rng.integers(0, ncols, (nb, bk)).astype(np.int32)
    bitmap = rng.integers(0, 256, (nb, bk)).astype(np.uint32)
    x = _rand(rng, nwin * WINDOW, kf)
    y = _rand(rng, ncols, kf)
    out = sddmm_mxu(jnp.asarray(cols), jnp.asarray(bitmap),
                    jnp.asarray(window), jnp.asarray(x), jnp.asarray(y),
                    kf_tile=128, interpret=True)
    expect = ref.sddmm_tc_ref(jnp.asarray(cols), jnp.asarray(bitmap),
                              jnp.asarray(window), jnp.asarray(x),
                              jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ntiles,ts,kf", [(2, 16, 128), (4, 32, 256)])
def test_sddmm_vpu_matches_ref(rng, ntiles, ts, kf):
    m, ncols = 40, 48
    rows = rng.integers(0, m, (ntiles, ts)).astype(np.int32)
    cols = rng.integers(0, ncols, (ntiles, ts)).astype(np.int32)
    x = _rand(rng, m, kf)
    y = _rand(rng, ncols, kf)
    out = sddmm_vpu(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(x),
                    jnp.asarray(y), kf_tile=128, interpret=True)
    expect = np.einsum("tjk,tjk->tj", x[rows], y[cols])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-4)


MATS = [
    random_uniform_csr(80, 64, 0.03, seed=11),
    banded_csr(64, 64, 8, 0.85, seed=12),
    mixed_csr(96, 96, seed=13),
    power_law_csr(64, 80, 5.0, seed=14),
]


@pytest.mark.parametrize("mi", range(len(MATS)))
@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hybrid_spmm_end_to_end(rng, mi, mode, backend):
    a = MATS[mi]
    b = _rand(rng, a.k, 48)
    oracle = ref.spmm_dense_oracle(a.to_dense(), b)
    op = LibraSpMM(a, mode=mode)
    out = np.asarray(op(jnp.asarray(b), backend=backend))
    np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("mi", range(len(MATS)))
@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hybrid_sddmm_end_to_end(rng, mi, mode, backend):
    a = MATS[mi]
    x = _rand(rng, a.m, 32)
    y = _rand(rng, a.k, 32)
    oracle = ref.sddmm_dense_oracle(a.to_dense(), x, y)
    op = LibraSDDMM(a, mode=mode)
    out = np.asarray(op(jnp.asarray(x), jnp.asarray(y), backend=backend))
    np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3)


def test_revalue_spmm_matches_fresh_plan(rng):
    """Runtime re-valuation must equal preprocessing a matrix with those
    values baked in (pattern fixed, values changed)."""
    a = MATS[2]
    plan = preprocess.preprocess_spmm(a)
    arrs = device_arrays(plan)
    new_vals = _rand(rng, a.nnz)
    arrs2 = ref.revalue_spmm_arrays(arrs, jnp.asarray(new_vals))
    b = _rand(rng, a.k, 24)
    out = spmm_apply(arrs2, jnp.asarray(b), m=a.m, nwin=num_windows(a.m),
                     backend="xla")
    import numpy as _np
    rows, cols, _ = a.to_coo()
    dense2 = _np.zeros((a.m, a.k), _np.float32)
    dense2[rows, cols] = new_vals
    np.testing.assert_allclose(np.asarray(out), dense2 @ b, rtol=1e-3,
                               atol=1e-3)


def test_bitmap_mask_bit_decoding():
    bm = jnp.asarray(np.array([[0b10000001, 0b00000010]], np.uint32))
    mask = np.asarray(ref.bitmap_mask(bm))[0]
    assert mask[0, 0] and mask[7, 0] and not mask[1, 0]
    assert mask[1, 1] and not mask[0, 1]


def _combine_maps(arrs):
    """The row/window maps the SpMM combines scatter with, per layout."""
    return {
        "tc_rank": arrs["tc_rank"],
        "tc_active_row": arrs["tc_active_row"],
        "tc_seg_window": arrs["tc_seg_row"][..., ::WINDOW] // WINDOW,
        "vpu_row": arrs["vpu_row"],
        "vpu_seg_row": arrs["vpu_seg_row"],
    }


@pytest.mark.parametrize("mi", range(len(MATS)))
@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
@pytest.mark.parametrize("n_shards", [1, 3])
def test_spmm_combine_maps_are_sorted(mi, mode, n_shards):
    """The combines scatter with ``indices_are_sorted=True``, which the
    TPU trusts without checking: every plan, sharded padding included,
    must keep its block windows and row maps non-decreasing."""
    from repro.api import ExecSpec
    from repro.dist.partition import partition_spmm

    a = MATS[mi]
    spec = ExecSpec(mode=mode, tune="model")
    if n_shards == 1:
        arrs = LibraSpMM(a, spec=spec).arrays.for_backend("pallas")
        arrs = dict(arrs, **LibraSpMM(a, spec=spec).arrays.for_backend(
            "pallas", segmented=False))
        stacks = [{k: np.asarray(v) for k, v in arrs.items()}]
    else:
        part = partition_spmm(a, n_shards, spec=spec)
        stacks = [{k: np.asarray(v[p]) for k, v in part.stacked.items()}
                  for p in range(n_shards)]
    for arrs in stacks:
        for name, m in _combine_maps(arrs).items():
            assert np.all(np.diff(m) >= 0), (name, m)
