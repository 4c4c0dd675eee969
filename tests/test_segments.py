"""Kernel-level hybrid load balancing (paper §4.3 Ts/Cs segments).

Covers the vectorized decomposition, the segment launch tables, the
atomic-flag invariants (every multi-producer output marked), bit-identity
of the tuned segment tables vs one block (tile) per segment and the
dense oracle on both backends, the one-unit tables vs the per-block /
per-tile host tensors, the refusal of caps below 1, empty-path edge
plans, Ts/Cs threading through the tuner + plan cache, and the dist
partitioner's segment-curve split.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import preprocess
from repro.core.balance import (
    BalanceParams,
    Segments,
    decompose_counts,
    segment_take,
)
from repro.core.formats import WINDOW, device_arrays
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.kernels import ref
from repro.sparse.generate import banded_csr, mixed_csr, power_law_csr
from repro.sparse.matrix import coo_to_csr
from repro.tune import TuneConfig


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _int_valued(a):
    """Same pattern, small positive integer values: float addition is
    exact, so segment re-association must be bitwise inert."""
    r = np.random.default_rng(7)
    return coo_to_csr(a.m, a.k, *a.to_coo()[:2],
                      r.integers(1, 4, a.nnz).astype(np.float32))


def _skewed(seed=3):
    """Power-law rows AND a hot dense window: window 0 exceeds any small
    Ts cap and the head rows exceed small Cs caps."""
    a = power_law_csr(128, 160, 8.0, alpha=1.4, seed=seed)
    rows, cols, _ = a.to_coo()
    # densify rows 0..7 (one full window) so its vectors pass any
    # threshold and decompose into many blocks
    hot_r = np.repeat(np.arange(8), 120)
    hot_c = np.tile(np.arange(120), 8)
    keep = ~np.isin(rows, np.arange(8))
    r = np.concatenate([rows[keep], hot_r])
    c = np.concatenate([cols[keep], hot_c])
    vals = np.random.default_rng(seed).integers(
        1, 4, r.size).astype(np.float32)
    return coo_to_csr(a.m, a.k, r, c, vals)


# ------------------------------------------------ decomposition (host) ---
def _decompose_scalar(counts, limit, shared):
    """The pre-vectorization per-owner append loop, kept as the oracle."""
    sizes, cur, atomic, start = [], [], [], []
    off = 0
    for i, c in enumerate(np.asarray(counts)):
        c = int(c)
        nseg = (c + limit - 1) // limit
        sh = bool(shared[i]) or nseg > 1
        for s in range(nseg):
            sizes.append(min(limit, c - s * limit))
            cur.append(i)
            atomic.append(sh)
            start.append(off + s * limit)
        off += c
    return (np.asarray(sizes, np.int64), np.asarray(cur, np.int64),
            np.asarray(atomic, bool), np.asarray(start, np.int64))


def test_decompose_counts_vectorized_matches_scalar():
    r = np.random.default_rng(1)
    for _ in range(25):
        n = int(r.integers(0, 40))
        counts = r.integers(0, 70, n)
        shared = r.integers(0, 2, n).astype(bool)
        limit = int(r.integers(1, 17))
        seg = decompose_counts(counts, limit, shared)
        sizes, cur, atomic, start = _decompose_scalar(counts, limit, shared)
        np.testing.assert_array_equal(seg.sizes, sizes)
        np.testing.assert_array_equal(seg.cur, cur)
        np.testing.assert_array_equal(seg.atomic, atomic)
        np.testing.assert_array_equal(seg.start, start)
        assert seg.limit == limit


def test_segment_take_padded_launch_table():
    seg = decompose_counts(np.asarray([5, 0, 2]), 4,
                           np.asarray([False, False, True]))
    take = segment_take(seg)
    assert take.shape == (seg.nseg, 4)
    # every unit covered exactly once; -1 beyond each ragged end
    units = take[take >= 0]
    np.testing.assert_array_equal(np.sort(units), np.arange(7))
    np.testing.assert_array_equal(take[0], [0, 1, 2, 3])
    np.testing.assert_array_equal(take[1], [4, -1, -1, -1])
    np.testing.assert_array_equal(take[2], [5, 6, -1, -1])
    # owner 0 decomposed -> atomic; owner 2 shared -> atomic
    assert seg.atomic.tolist() == [True, True, True]


def test_segment_tables_cover_plan_exactly():
    a = _skewed()
    cfg = TuneConfig(ts=2, cs=64, bk=8, ts_tile=16)
    plan = preprocess.preprocess_spmm(a, cfg=cfg)
    tc_seg = plan.meta["tc_segments"]
    vpu_seg = plan.meta["vpu_segments"]
    assert (tc_seg.sizes <= 2).all() and tc_seg.sizes.min() >= 1
    take = segment_take(tc_seg)
    np.testing.assert_array_equal(np.sort(take[take >= 0]),
                                  np.arange(plan.tc.nblk))
    # segments never straddle windows
    np.testing.assert_array_equal(plan.tc.window[take[take >= 0]],
                                  np.repeat(tc_seg.cur,
                                            tc_seg.sizes.astype(int)))
    # VPU: tiles covered once, owners are rows, sizes ≤ cs/ts_tile
    vt = segment_take(vpu_seg)
    np.testing.assert_array_equal(np.sort(vt[vt >= 0]),
                                  np.arange(plan.vpu.ntiles))
    assert (vpu_seg.sizes <= 64 // 16).all()
    np.testing.assert_array_equal(plan.vpu.row[vt[vt >= 0]],
                                  np.repeat(vpu_seg.cur,
                                            vpu_seg.sizes.astype(int)))
    # the hot window decomposed
    assert (np.bincount(tc_seg.cur.astype(int))[0]) > 1


def test_atomic_marks_every_multi_producer_output():
    a = _skewed()
    plan = preprocess.preprocess_spmm(
        a, cfg=TuneConfig(ts=2, cs=32, bk=8, ts_tile=16))
    tc_seg = plan.meta["tc_segments"]
    vpu_seg = plan.meta["vpu_segments"]
    # TC writes whole windows, VPU writes single rows: an output is
    # multi-producer when a window has >1 TC segment, a row has >1 VPU
    # segment, or a TC window also contains VPU rows (the paper's
    # window-1 rule). VPU segments on *different* rows never collide.
    nwin = (a.m + WINDOW - 1) // WINDOW
    tc_per_win = np.bincount(tc_seg.cur.astype(int), minlength=nwin)
    vpu_per_win = np.bincount((vpu_seg.cur // WINDOW).astype(int),
                              minlength=nwin)
    vpu_per_row = np.bincount(vpu_seg.cur.astype(int), minlength=a.m)
    tc_multi = (tc_per_win > 1) | (vpu_per_win > 0)
    assert tc_seg.atomic[tc_multi[tc_seg.cur.astype(int)]].all()
    vpu_multi = ((vpu_per_row[vpu_seg.cur.astype(int)] > 1)
                 | (tc_per_win[(vpu_seg.cur // WINDOW).astype(int)] > 0))
    assert vpu_seg.atomic[vpu_multi].all()
    # and the skewed fixture actually exercises every case
    assert (tc_per_win > 1).any() and (vpu_per_row > 1).any()


# ------------------------------------------------- segmented execution ---
def _check_bitident_spmm(a, cfg, n=64):
    r = np.random.default_rng(2)
    b = jnp.asarray(r.integers(-2, 3, (a.k, n)).astype(np.float32))
    op = LibraSpMM(a, tune=cfg)
    op0 = LibraSpMM(a, tune=cfg.replace(ts=1, cs=op.plan.vpu.ts))
    assert op0.plan.meta["tc_segments"].nseg == op0.plan.tc.nblk
    assert op0.plan.meta["vpu_segments"].nseg == op0.plan.vpu.ntiles
    # Both streams fetch real elements only.
    for o in (op, op0):
        assert int(np.asarray(o.arrays["vpu_seg_len"]).sum()) == o.plan.vpu.nnz
    oracle = np.asarray(a.to_dense() @ np.asarray(b), np.float32)
    outs = [np.asarray(op(b, backend=be)) for be in ("xla", "pallas")]
    outs += [np.asarray(op0(b, backend=be)) for be in ("xla", "pallas")]
    for out in outs:
        assert np.array_equal(out, outs[0])
        np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-5)


def test_segmented_spmm_bit_identical_window_exceeds_ts(rng):
    # window 0 has 120 dense vectors -> 15 blocks at bk=8 -> 8 segments
    _check_bitident_spmm(_skewed(), TuneConfig(ts=2, cs=64, bk=8,
                                               ts_tile=16))


def test_segmented_spmm_bit_identical_rows_exceed_cs(rng):
    # ts_tile=8, cs=16 -> 2 tiles per segment; power-law head rows have
    # dozens of residual nnz -> many segments per row
    a = _int_valued(power_law_csr(96, 120, 10.0, alpha=1.3, seed=9))
    _check_bitident_spmm(a, TuneConfig(ts=4, cs=16, ts_tile=8))


def test_segmented_spmm_model_tuned_corpus_mats(rng):
    for gen in (lambda: mixed_csr(61, 93, seed=4),
                lambda: banded_csr(64, 256, 48, 1.0, seed=10)):
        _check_bitident_spmm(_int_valued(gen()), TuneConfig())


def test_segmented_empty_tc_and_empty_vpu_plans(rng):
    a = _int_valued(mixed_csr(72, 64, seed=5))
    b = jnp.asarray(rng.integers(-2, 3, (a.k, 32)).astype(np.float32))
    oracle = np.asarray(a.to_dense() @ np.asarray(b), np.float32)
    for mode in ("tcu", "vpu"):
        op = LibraSpMM(a, mode=mode, tune=TuneConfig(ts=2, cs=64))
        empty_seg = (op.plan.meta["vpu_segments"] if mode == "tcu"
                     else op.plan.meta["tc_segments"])
        assert empty_seg.nseg == 0  # dummy segment materialized on device
        for be in ("xla", "pallas"):
            assert np.array_equal(np.asarray(op(b, backend=be)), oracle)


def test_segmented_sddmm_bit_identical(rng):
    a = _skewed(seed=6)
    x = jnp.asarray(rng.integers(-2, 3, (a.m, 48)).astype(np.float32))
    y = jnp.asarray(rng.integers(-2, 3, (a.k, 48)).astype(np.float32))
    cfg = TuneConfig(ts=2, cs=64, ts_tile=16)
    op = LibraSDDMM(a, tune=cfg)
    op0 = LibraSDDMM(a, tune=cfg.replace(ts=1, cs=op.plan.vpu.ts))
    assert op.plan.meta["seg_spt"] > 1 and op0.plan.meta["seg_spt"] == 1
    assert op0.plan.meta["tc_segments"].nseg == op0.plan.tc.nblk
    oracle = np.asarray(ref.sddmm_dense_oracle(
        a.to_dense(), np.asarray(x), np.asarray(y)))
    outs = [np.asarray(op(x, y, backend=be)) for be in ("xla", "pallas")]
    outs += [np.asarray(op0(x, y, backend=be)) for be in ("xla", "pallas")]
    for out in outs:
        assert np.array_equal(out, outs[0])
        np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-5)


def test_segmented_revalue_matches_rebaked_plan(rng):
    a = _int_valued(power_law_csr(80, 72, 7.0, seed=8))
    op = LibraSpMM(a, tune=TuneConfig(ts=2, cs=32, bk=8, ts_tile=8))
    ev = rng.integers(-3, 4, (a.nnz,)).astype(np.float32)
    arrs2 = ref.revalue_spmm_arrays(op.arrays, jnp.asarray(ev))
    b = jnp.asarray(rng.integers(-2, 3, (a.k, 24)).astype(np.float32))
    from repro.core.windows import num_windows
    from repro.kernels.ops import spmm_apply

    out = np.asarray(spmm_apply(arrs2, b, m=a.m, nwin=num_windows(a.m),
                                backend="pallas", cfg=op.tune_config))
    dense = np.zeros((a.m, a.k), np.float32)
    r, c, _ = a.to_coo()
    dense[r, c] = ev
    assert np.array_equal(out, np.asarray(dense @ np.asarray(b), np.float32))


# ----------------------------------------------- one-unit segments ---
@pytest.mark.parametrize("mode", ["hybrid", "tcu", "vpu"])
def test_one_unit_tables_are_the_per_block_tensors(mode):
    """One block (``ts=1``) and one tile (``cs=ts_tile``) per segment:
    the launch tables are the per-block / per-tile host tensors, element
    for element, same shapes and dtypes — for SpMM's MXU and VPU streams
    and SDDMM's. SDDMM's VPU table is the per-tile one whenever a
    segment holds one tile, whatever ``ts``."""
    from repro.core.formats import _host_arrays

    a = _skewed(seed=4)
    cfg = TuneConfig(ts=1, cs=16, bk=8, ts_tile=16)

    def same(table, tensor):
        assert table.dtype == tensor.dtype and np.array_equal(table, tensor)

    plan = preprocess.preprocess_spmm(
        a, preprocess.threshold_for_mode_spmm(mode), cfg=cfg)
    host, tc, vpu = _host_arrays(plan), plan.tc, plan.vpu
    same(host["tc_seg_vals"], tc.vals)
    same(host["tc_seg_cols"], tc.cols)
    same(host["tc_seg_pos"], tc.pos)
    same(host["tc_seg_row"].reshape(-1, WINDOW)[:, 0] // WINDOW, tc.window)
    same(host["vpu_seg_vals"], vpu.vals)
    same(host["vpu_seg_cols"], vpu.cols)
    same(host["vpu_seg_pos"], vpu.pos)
    same(host["vpu_seg_row"], vpu.row)
    for sd_cfg in (cfg, cfg.replace(ts=None)):
        plan = preprocess.preprocess_sddmm(
            a, preprocess.threshold_for_mode_sddmm(mode, 8), cfg=sd_cfg)
        host, tc, vpu = _host_arrays(plan), plan.tc, plan.vpu
        assert plan.meta["seg_spt"] == 1
        same(host["vpu_seg_rows"], vpu.rows)
        same(host["vpu_seg_cols"], vpu.cols)
        same(host["vpu_seg_out_pos"], vpu.out_pos)
        same(host["vpu_seg_mask"], vpu.mask)
    plan = preprocess.preprocess_sddmm(
        a, preprocess.threshold_for_mode_sddmm(mode, 8), cfg=cfg)
    host, tc = _host_arrays(plan), plan.tc
    same(host["tc_seg_cols"], tc.cols)
    same(host["tc_seg_bitmap"], tc.bitmap)
    same(host["tc_seg_window"], tc.window)
    same(host["tc_seg_out_pos"], plan.tc_out_pos)


@pytest.mark.parametrize("op", [LibraSpMM, LibraSDDMM])
@pytest.mark.parametrize("cfg", [TuneConfig(ts=0), TuneConfig(cs=0)],
                         ids=["ts", "cs"])
def test_segment_caps_below_one_are_refused(op, cfg):
    """Every plan launches from its segment tables: a cap of 0 names no
    launch and fails the plan build."""
    with pytest.raises(ValueError, match="segment caps"):
        op(mixed_csr(32, 32, seed=1), tune=cfg)


# ------------------------------------------------- segment lengths ---
def _len_corpus():
    from repro.sparse import suitesparse_like_corpus

    mats = dict(suitesparse_like_corpus(n_small=4, seed=7))
    mats["skewed"] = _skewed()
    return mats


@pytest.mark.parametrize("name", sorted(_len_corpus()))
def test_vpu_seg_len_counts_a_real_prefix(name):
    """``vpu_seg_len`` counts each segment's real elements, they fill a
    prefix of its slots (what lets the kernel fetch just that prefix),
    and the reorder remap of the plan's nnz maps leaves it unchanged."""
    from repro.core.formats import _host_arrays, spmm_vpu_seg_len

    a = _len_corpus()[name]
    for cfg in (TuneConfig(), TuneConfig(ts=2, cs=16, ts_tile=8)):
        plan = preprocess.preprocess_spmm(a, cfg=cfg)
        host = _host_arrays(plan)
        lens, real = host["vpu_seg_len"], host["vpu_seg_pos"] >= 0
        assert lens.dtype == np.int32
        assert lens.shape == host["vpu_seg_row"].shape
        assert np.array_equal(lens, real.sum(axis=1))
        slots = np.arange(real.shape[1])[None, :]
        assert np.array_equal(real, slots < lens[:, None])
        assert np.array_equal(spmm_vpu_seg_len(plan), lens)
        assert int(lens.sum()) == plan.vpu.nnz
        perm = np.random.default_rng(3).permutation(a.nnz)
        remapped = preprocess._remap_spmm_plan(plan, perm)
        assert np.array_equal(_host_arrays(remapped)["vpu_seg_len"], lens)


def test_vpu_seg_len_of_a_plan_without_vpu_work():
    a = _int_valued(mixed_csr(72, 64, seed=5))
    op = LibraSpMM(a, mode="tcu", tune=TuneConfig(ts=2, cs=64))
    assert op.plan.meta["vpu_segments"].nseg == 0
    # the dummy all-padding segment fetches nothing
    assert np.array_equal(np.asarray(op.arrays["vpu_seg_len"]), [0])


def test_partition_stacks_vpu_seg_len_with_zero_padding():
    from repro.dist.partition import partition_spmm

    a = _skewed(seed=11)
    part = partition_spmm(a, 3, tune="off")
    st = {k: np.asarray(v) for k, v in part.stacked.items()}
    lens, real = st["vpu_seg_len"], st["vpu_seg_pos"] >= 0
    assert lens.shape == st["vpu_seg_row"].shape
    assert np.array_equal(lens, real.sum(axis=-1))
    slots = np.arange(real.shape[-1])
    assert np.array_equal(real, slots < lens[..., None])
    # shards with fewer segments are padded with empty ones: length 0
    empty = ~real.any(axis=-1)
    assert empty.any() and (lens[empty] == 0).all()


# --------------------------------------------------- tuner / cache ---
def test_ts_cs_thread_through_tuner_and_plan():
    a = power_law_csr(128, 128, 12.0, seed=2)
    op = LibraSpMM(a, tune="model")
    cfg = op.tune_config
    assert cfg.ts is not None and cfg.ts >= 1
    assert cfg.cs is not None and cfg.cs >= (cfg.ts_tile or 32)
    bal = op.plan.meta["balance"]
    assert bal.ts == cfg.ts and bal.cs == cfg.cs
    assert op.plan.meta["tc_segments"].limit == cfg.ts
    # explicit balance still wins over cfg
    plan = preprocess.preprocess_spmm(
        a, cfg=cfg, balance=BalanceParams(ts=1, cs=32))
    assert plan.meta["tc_segments"].limit == 1


def test_ts_cs_cache_roundtrip(tmp_path):
    from repro.tune import PlanCache
    from repro.tune.cache import CACHE_VERSION, tune_key

    assert CACHE_VERSION >= 3  # v3: ts/cs joined TuneConfig
    pc = PlanCache(str(tmp_path))
    cfg = TuneConfig(ts=4, cs=128, nt=256, source="search")
    key = tune_key(power_law_csr(32, 32, 4.0, seed=1), op="spmm",
                   width=128, dtype="float32", backend="xla",
                   mode="hybrid", tune="search")
    pc.put(key, cfg)
    got = pc.get(key)
    assert got.ts == 4 and got.cs == 128 and got.nt == 256


def test_search_perturbs_segment_caps():
    from repro.tune.search import spmm_candidates

    a = power_law_csr(96, 96, 8.0, seed=4)
    cands = spmm_candidates(a, n=128, mode="hybrid", threshold=None,
                            backend="pallas")
    model = [c for c in cands if c.source == "model"][0]
    ts_vals = {c.ts for c in cands}
    cs_vals = {c.cs for c in cands}
    assert len(ts_vals) > 1 or model.ts in (1, 64)
    assert len(cs_vals) > 1 or model.cs in (model.ts_tile, 16 * model.ts_tile)


def test_vmem_model_charges_segment_widths():
    from repro.tune import vmem_spmm_bytes

    small = vmem_spmm_bytes(TuneConfig(ts=1, cs=32), bk=32, ts=32)
    big = vmem_spmm_bytes(TuneConfig(ts=16, cs=512), bk=32, ts=32)
    assert big > small


# ------------------------------------------------------ dist segment curve ---
def test_partition_balances_on_segment_curve():
    from repro.dist.partition import partition_spmm, segment_curve

    a = _skewed(seed=11)
    part = partition_spmm(a, 4, tune="off")
    assert "segment_balance" in part.meta
    assert len(part.meta["shard_segments"]) == 4
    assert part.meta["segment_balance"]["max_over_mean"] >= 1.0
    curve = segment_curve(a, op="spmm", threshold=3, bk=32, seg_ts=8,
                          seg_cs=128)
    assert curve.shape == ((a.m + WINDOW - 1) // WINDOW,)
    # shard boundaries follow the curve: per-shard curve mass within one
    # window's mass of the ideal split
    bounds = [ (s.win_start, s.win_end) for s in part.shards ]
    ideal = curve.sum() / 4
    for w0, w1 in bounds:
        assert curve[w0:w1].sum() <= ideal + max(curve.max(), 1)


def test_partition_segmented_sharded_apply_bit_identical(rng):
    """The vmap emulation of the sharded apply (the per-device program)
    with stacked segment tables must match the single-device segmented
    apply bitwise on integer data — on both backends."""
    import jax

    from repro.dist.partition import partition_spmm

    a = _int_valued(power_law_csr(96, 80, 9.0, seed=12))
    part = partition_spmm(a, 3, tune="off")
    assert "tc_seg_vals" in part.stacked
    b = jnp.asarray(rng.integers(-2, 3, (a.k, 32)).astype(np.float32))
    op = LibraSpMM(a, tune="off")
    from repro.kernels.ops import spmm_apply

    for backend in ("xla", "pallas"):
        def body(local):
            arrs = {k: v for k, v in local.items() if k != "halo"}
            b_halo = jnp.take(b, local["halo"], axis=0)
            return spmm_apply(arrs, b_halo, m=part.rows_pad,
                              nwin=part.wmax, backend=backend,
                              cfg=part.run_cfg)
        out = jax.vmap(body)(part.stacked)
        got = np.asarray(jnp.take(out.reshape(-1, b.shape[1]),
                                  part.out_gather, axis=0))
        want = np.asarray(op(b, backend=backend))
        assert np.array_equal(got, want), backend


def test_partition_empty_matrix_segment_curve():
    """m=0: the segment curve must trim the padded feature histogram to
    zero windows so shard_windows' weights contract holds (regression:
    this crashed with a shape assertion)."""
    from repro.dist.partition import partition_sddmm, partition_spmm
    from repro.sparse.matrix import SparseCSR

    a = SparseCSR(0, 5, np.zeros(1, np.int64), np.zeros(0, np.int32),
                  np.zeros(0, np.float32))
    assert partition_spmm(a, 2, tune="off").n_shards == 2
    assert partition_sddmm(a, 2, tune="off").n_shards == 2


def test_segments_dataclass_replace_and_empty():
    seg = decompose_counts(np.zeros(5, np.int64), 4, np.zeros(5, bool))
    assert seg.nseg == 0 and seg.limit == 4
    seg2 = dataclasses.replace(seg, limit=8)
    assert isinstance(seg2, Segments) and seg2.limit == 8
