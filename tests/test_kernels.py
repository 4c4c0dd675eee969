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


def _prefix_tiles(rng, lens, ts, k, cols_lo=0):
    """Tiles whose first ``lens[t]`` slots are real (values and columns
    in ``[cols_lo, k)``) and whose padding is value 0, column 0."""
    real = np.arange(ts)[None, :] < np.asarray(lens)[:, None]
    vals = np.where(real, _rand(rng, len(lens), ts), 0.0).astype(np.float32)
    cols = np.where(real, rng.integers(cols_lo, k, (len(lens), ts)),
                    0).astype(np.int32)
    return vals, cols


# Segment lengths at cs = 32: every case holds 0, 1, cs − 1 and cs;
# "zero_group" has a whole group of 8 empty segments, "pad_rows" 13
# segments, so the wrapper pads 3 rows, and "many_blocks" 1100, so the
# lengths span two SMEM blocks.
BOUNDED_LENS = {
    "ragged": [0, 1, 31, 32, 5, 17, 0, 32, 2, 32, 31, 1, 0, 9, 16, 3],
    "zero_group": [32, 1, 0, 31, 7, 7, 32, 2] + [0] * 8
                  + [31, 0, 1, 32, 12, 30, 0, 4],
    "pad_rows": [1, 0, 32, 31, 3, 29, 0, 32, 1, 31, 8, 0, 32],
    "many_blocks": [7 * t % 33 for t in range(1100)],
}


@pytest.mark.parametrize("n", [128, 256])
@pytest.mark.parametrize("grid_order", ["n_outer", "block_outer"])
@pytest.mark.parametrize("case", sorted(BOUNDED_LENS))
def test_spmm_vpu_bounded_fetch_bit_identical(rng, case, grid_order, n):
    """With finite B, fetching only each segment's real prefix gives the
    every-slot kernel's output bit for bit."""
    lens = np.asarray(BOUNDED_LENS[case], np.int32)
    ts, k = 32, 48
    vals, cols = _prefix_tiles(rng, lens, ts, k)
    b = _rand(rng, k, n)
    args = (jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(b))
    every = spmm_vpu(*args, nt=128, grid_order=grid_order, interpret=True)
    bounded = spmm_vpu(*args, jnp.asarray(lens), nt=128,
                       grid_order=grid_order, interpret=True)
    assert np.array_equal(np.asarray(every), np.asarray(bounded))
    expect = np.einsum("tj,tjn->tn", vals, b[cols])
    np.testing.assert_allclose(np.asarray(bounded), expect, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("bad", [np.inf, np.nan], ids=["inf", "nan"])
@pytest.mark.parametrize("grid_order", ["n_outer", "block_outer"])
def test_spmm_vpu_bounded_fetch_ignores_stale_rows(rng, grid_order, bad):
    """Slots past a segment's length keep whatever an earlier grid step
    fetched: here a B row of ``inf``/``nan`` that only the first group's
    real slots name. Every later group stays finite and exact."""
    ts, k, n, poison = 32, 40, 256, 3
    later = [0, 1, 31, 2, 0, 5, 1, 0, 4, 0, 0, 1, 30, 0, 2, 7]
    lens = np.asarray([ts] * 8 + later, np.int32)
    vals, cols = _prefix_tiles(rng, lens, ts, k, cols_lo=poison + 1)
    cols[:8] = poison
    b = _rand(rng, k, n)
    b[poison] = bad
    args = (jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(b))
    bounded = np.asarray(spmm_vpu(*args, jnp.asarray(lens), nt=128,
                                  grid_order=grid_order, interpret=True))
    every = np.asarray(spmm_vpu(*args, nt=128, grid_order=grid_order,
                                interpret=True))
    assert np.isfinite(bounded[8:]).all()
    assert np.array_equal(bounded[8:], every[8:])
    expect = np.einsum("tj,tjn->tn", vals[8:], b[cols[8:]])
    np.testing.assert_allclose(bounded[8:], expect, rtol=1e-4, atol=1e-4)


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


# A 256-lane tile holds a whole width-256 call: one head, 4 heads of 64,
# or 4 heads of 40 (160 features padded to 256, head 3 straddling the
# two 128-lane tiles of the narrower tile).
WIDE_LAYOUTS = {"one": (None, 256), "h4c64": (4, 64), "h4c40": (4, 40)}


def _wide_layout(rng, layout, k, lead, integer=False):
    """Values of shape ``lead`` (plus a head axis for multi-head) and a
    ``(k, 256)`` B whose columns past ``H·c`` are zero, as ops.py pads
    them; small integers where float sums must be exact in any order."""
    heads, c = WIDE_LAYOUTS[layout]
    draw = ((lambda *sh: rng.integers(-3, 4, sh).astype(np.float32))
            if integer else (lambda *sh: _rand(rng, *sh)))
    vals = draw(*lead, *((heads,) if heads else ()))
    b = np.zeros((k, 256), np.float32)
    b[:, :(heads or 1) * c] = draw(k, (heads or 1) * c)
    return vals, b, heads, (c if heads else None)


def _per_head(b, heads, c):
    """``(k, n)`` → per-lane head index, ``H − 1`` past ``H·c``."""
    return np.minimum(np.arange(b.shape[1]) // c, heads - 1)


WIDE_SPMM_CASES = (
    [("vpu", lay, order, bounded) for lay in WIDE_LAYOUTS
     for order in ("n_outer", "block_outer") for bounded in (False, True)]
    + [("mxu", lay, order, False) for lay in WIDE_LAYOUTS
       for order in ("n_outer", "block_outer")])


@pytest.mark.parametrize("kernel,layout,grid_order,bounded", WIDE_SPMM_CASES)
def test_spmm_kernels_at_a_256_lane_tile_match_two_128_tiles(
        rng, kernel, layout, grid_order, bounded):
    """One 256-lane tile gives the output of two 128-lane tiles bit for
    bit (each lane's sum is the same sum), for one head and four, with
    and without segment lengths, in both grid orders; and it matches
    the dense products."""
    k = 48
    if kernel == "vpu":
        lens = np.asarray(BOUNDED_LENS["pad_rows"], np.int32)
        ts = 32
        vals, b, heads, c = _wide_layout(rng, layout, k, (len(lens), ts))
        real = np.arange(ts)[None, :] < lens[:, None]
        vals = np.where(real[..., None] if heads else real, vals, 0.0)
        cols = np.where(real, rng.integers(0, k, real.shape), 0)
        args = [jnp.asarray(vals.astype(np.float32)),
                jnp.asarray(cols.astype(np.int32)), jnp.asarray(b)]
        if bounded:
            args.append(jnp.asarray(lens))
        run = lambda nt: np.asarray(spmm_vpu(  # noqa: E731
            *args, nt=nt, grid_order=grid_order, head_dim=c,
            interpret=True))
        per_slot = vals if heads else vals[..., None]
        gathered = b[cols]                                   # (t, ts, n)
        if heads:
            expect = np.einsum("tjn,tjn->tn", np.take(
                per_slot, _per_head(b, heads, c), axis=2), gathered)
        else:
            expect = np.einsum("tj,tjn->tn", vals, gathered)
    else:
        nb, bk = 5, 16
        vals, b, heads, c = _wide_layout(rng, layout, k, (nb, WINDOW, bk),
                                         integer=True)
        cols = rng.integers(0, k, (nb, bk)).astype(np.int32)
        args = [jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(b)]
        run = lambda nt: np.asarray(spmm_mxu(  # noqa: E731
            *args, nt=nt, grid_order=grid_order, head_dim=c,
            interpret=True))
        if heads:
            lane_vals = np.take(vals, _per_head(b, heads, c), axis=3)
            expect = np.einsum("brjn,bjn->brn", lane_vals,
                               b[cols]).reshape(nb * WINDOW, -1)
        else:
            expect = np.einsum("brj,bjn->brn", vals,
                               b[cols]).reshape(nb * WINDOW, -1)
    wide, narrow = run(256), run(128)
    assert np.array_equal(wide, narrow)
    np.testing.assert_allclose(wide, expect, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", sorted(WIDE_LAYOUTS))
@pytest.mark.parametrize("kernel", ["mxu", "vpu"])
def test_sddmm_kernels_at_a_256_feature_tile_match_two_128_tiles(
        rng, kernel, layout):
    """One 256-feature tile scores as two 128-feature tiles summed, to
    float32 rounding (one dot of 256 features in place of two of 128),
    for one head and four; and it matches the dense products."""
    heads, c = WIDE_LAYOUTS[layout]
    m, ncols = 24, 40
    width = (heads or 1) * c
    x = np.zeros((m, 256), np.float32)
    y = np.zeros((ncols, 256), np.float32)
    x[:, :width] = _rand(rng, m, width)
    y[:, :width] = _rand(rng, ncols, width)
    hd = _per_head(x, heads, c) if heads else np.zeros(256, int)
    hd = np.where(np.arange(256) < width, hd, -1)
    per_head = np.stack([np.where(hd == h, 1.0, 0.0)
                         for h in range(heads or 1)])      # (H, 256)
    if kernel == "mxu":
        nb, bk = 4, 16
        window = np.sort(rng.integers(0, m // WINDOW, nb)).astype(np.int32)
        cols = rng.integers(0, ncols, (nb, bk)).astype(np.int32)
        bitmap = rng.integers(0, 256, (nb, bk)).astype(np.uint32)
        run = lambda kt: np.asarray(sddmm_mxu(  # noqa: E731
            jnp.asarray(cols), jnp.asarray(bitmap), jnp.asarray(window),
            jnp.asarray(x), jnp.asarray(y), kf_tile=kt, heads=heads,
            head_dim=c if heads else None, interpret=True))
        xw = x.reshape(-1, WINDOW, 256)[window]                # (nb, 8, f)
        s = np.einsum("brf,hf,bjf->bhrj", xw, per_head, y[cols])
        bits = (bitmap[:, None, None, :].astype(np.int64)
                >> np.arange(WINDOW)[None, None, :, None]) & 1
        expect = np.where(bits > 0, s, 0.0)
        if not heads:
            expect = expect[:, 0]
    else:
        ntiles, ts = 3, 16
        rows = rng.integers(0, m, (ntiles, ts)).astype(np.int32)
        cols = rng.integers(0, ncols, (ntiles, ts)).astype(np.int32)
        run = lambda kt: np.asarray(sddmm_vpu(  # noqa: E731
            jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(x),
            jnp.asarray(y), kf_tile=kt, heads=heads,
            head_dim=c if heads else None, interpret=True))
        expect = np.einsum("tjf,hf,tjf->htj", x[rows], per_head, y[cols])
        if not heads:
            expect = expect[0]
    wide, narrow = run(256), run(128)
    np.testing.assert_allclose(wide, narrow, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(wide, expect, rtol=1e-4, atol=1e-4)


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
