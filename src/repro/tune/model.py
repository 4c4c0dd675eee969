"""Occupancy-aware analytical tuner (paper §4.2 + §4.4 choices, modeled).

The paper's gains come from *choosing well* per sparsity pattern: the
2D-aware workload distribution picks the TC/VPU split, and
occupancy-aware task scheduling sizes work to the hardware. This module
makes those choices analytically — no timing — from cheap matrix
features:

* a **vector histogram** (per window, how many 8×1 column vectors have
  1..8 non-zeros — the Fig.-1 statistic at full resolution), which
  prices every candidate threshold through the same roofline formulas as
  :mod:`repro.core.threshold` *without building a plan per candidate*;
* a **VMEM footprint model** for each of the four kernels: the bytes a
  single pipelined grid step keeps resident, as Mosaic lays them out
  (Pallas double-buffers the pipelined blocks, hence the ×2, in whole
  (8, 128) tiles; the DMA'd rows scratch is single, one sublane per
  row, and the SpMM kernels add a relayout of it). Lane-tile caps
  (``nt``, ``kf_tile``) and the §4.3 segment caps are chosen as the
  largest hardware-aligned candidates whose footprint stays inside
  ``VMEM_BUDGET_BYTES`` — the TPU analogue of CUDA occupancy sizing.
  The kernels fetch operand rows by id, so no footprint grows with
  ``k``, ``m`` or ``kcols``;
* a **per-call tile** (:func:`lane_tile`): each apply takes the tile
  under the cap that covers its own width in the fewest tiles, so a
  dense row is copied once per call wherever the cap and the budget
  allow, whatever width the plan was tuned at.

The result is a :class:`TuneConfig` — the single object every layer
(preprocess, ops, kernels, benchmarks) parameterizes through.

Heads. A plan is tuned once per graph and serves every width and head
count (:mod:`repro.kernels.gather` states the multi-head layout). Any
lane tile fits that layout: the kernels map each lane to its head, so a
head may straddle two tiles and no tile has to hold whole heads. What
grows with ``H`` heads is a step's value or score block (``H`` values
per slot, ``H`` score rows), a few KiB at the tuned caps, and
``spmm_mxu``'s stacked dot operand (a copy of the fetched rows a head).
The tuner prices one head; :func:`lane_tile` prices the call's heads,
and narrows the tile where their copies do not fit.
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np

from repro.core.formats import WINDOW
from repro.core.threshold import HardwareModel
from repro.sparse.matrix import SparseCSR

# Per-core VMEM on current TPUs is ~16 MiB; leave headroom for Mosaic's
# own scratch + the scalar-prefetch operands.
VMEM_BYTES_TOTAL = 16 * 2**20
VMEM_BUDGET_BYTES = int(VMEM_BYTES_TOTAL * 0.75)

# Hardware-aligned lane-tile candidates (lane width 128).
_NT_CANDIDATES = (512, 256, 128)
_KF_CANDIDATES = (512, 256, 128)


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """One plan-selection decision, consumed by every layer.

    ``threshold``/``bk``/``ts_tile`` parameterize preprocessing (the
    2D-aware distribution); ``nt`` the SpMM kernels; ``kf_tile`` the
    SDDMM kernels (``nt`` and ``kf_tile`` cap the tile :func:`lane_tile`
    derives from each call's width). ``None`` means "the operator
    default" so a bare ``TuneConfig()`` reproduces the untuned behavior.
    Frozen + hashable so it can ride through ``jax.jit`` as a static
    argument.
    """

    nt: int = 128            # widest SpMM lane tile a call may take
    kf_tile: int = 128       # widest SDDMM feature tile a call may take
    threshold: int | None = None  # TC/VPU split (None = operator default)
    bk: int | None = None    # condensed block depth (None = operator default)
    ts_tile: int | None = None    # VPU tile width (None = operator default)
    # Hybrid load balancing caps (paper §4.3 Ts/Cs): ``ts`` TC blocks per
    # MXU segment and ``cs`` VPU elements per row-segment bound the work
    # one grid step does. None = operator default (segmentation on);
    # 0 disables segmentation (the pre-§4.3 per-block/per-tile launch).
    ts: int | None = None
    cs: int | None = None
    source: str = "default"  # default | model | search | cache

    def replace(self, **kw) -> "TuneConfig":
        return dataclasses.replace(self, **kw)


DEFAULT_TUNE = TuneConfig()


@dataclasses.dataclass(frozen=True)
class MatrixFeatures:
    """Cheap pattern statistics driving the analytical tuner."""

    m: int
    k: int
    nnz: int
    nwin: int
    row_hist: np.ndarray   # (m,) nnz per row
    win_vec_hist: np.ndarray  # (nwin, WINDOW+1) vectors per window by count
    # win_vec_hist[w, c] = number of 8×1 column vectors in window w with
    # exactly c non-zeros (c in 1..WINDOW; column 0 unused).

    @property
    def window_density(self) -> float:
        """Mean fraction of occupied sublanes over non-empty vectors."""
        counts = np.arange(WINDOW + 1)
        tot_vec = self.win_vec_hist.sum()
        if tot_vec == 0:
            return 0.0
        occ = (self.win_vec_hist * counts[None, :]).sum()
        return float(occ / (tot_vec * WINDOW))

    def vectors_at_least(self, threshold: int) -> np.ndarray:
        """Per-window count of vectors with ≥ ``threshold`` non-zeros."""
        t = int(np.clip(threshold, 1, WINDOW + 1))
        return self.win_vec_hist[:, t:].sum(axis=1)

    def nnz_at_least(self, threshold: int) -> int:
        """Total non-zeros living in vectors with ≥ ``threshold`` nnz."""
        t = int(np.clip(threshold, 1, WINDOW + 1))
        counts = np.arange(WINDOW + 1)
        return int((self.win_vec_hist[:, t:] * counts[None, t:]).sum())


def matrix_features(a: SparseCSR) -> MatrixFeatures:
    """One vectorized pass: row histogram + per-window vector histogram."""
    rows, cols, _ = a.to_coo()
    nwin = (a.m + WINDOW - 1) // WINDOW
    row_hist = np.diff(a.indptr).astype(np.int64)
    win_vec_hist = np.zeros((max(nwin, 1), WINDOW + 1), np.int64)
    if rows.size:
        win = (rows // WINDOW).astype(np.int64)
        order = np.lexsort((cols, win))
        winS, colS = win[order], cols[order]
        newvec = np.ones(winS.size, bool)
        newvec[1:] = (winS[1:] != winS[:-1]) | (colS[1:] != colS[:-1])
        vec_id = np.cumsum(newvec) - 1
        vec_count = np.bincount(vec_id)
        vec_win = winS[newvec]
        np.add.at(win_vec_hist, (vec_win, vec_count), 1)
    return MatrixFeatures(a.m, a.k, a.nnz, nwin, row_hist, win_vec_hist)


# --------------------------------------------------------------- VMEM ---
def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def _seg_widths(cfg: TuneConfig, *, bk: int, ts_tile: int) -> tuple[int, int]:
    """Effective per-grid-step work widths under the §4.3 segment caps:
    condensed vectors per MXU segment (``ts`` blocks × ``bk``) and VPU
    elements per row-segment (``cs`` rounded down to whole tiles).
    ``ts``/``cs`` of 0 disable segmentation (one block / one tile per
    step — the legacy launch)."""
    from repro.core.balance import BalanceParams

    dflt = BalanceParams()
    seg_ts = dflt.ts if cfg.ts is None else cfg.ts
    seg_cs = dflt.cs if cfg.cs is None else cfg.cs
    mxu_vecs = max(1, seg_ts) * bk
    vpu_els = max(1, seg_cs // max(ts_tile, 1)) * ts_tile
    return mxu_vecs, vpu_els


# Mosaic's own temporaries in a step beyond the blocks and scratches
# charged below: up to 88 KiB in compiles for a described v5e, at every
# shape ``tests/test_tpu_compile.py`` checks.
_MOSAIC_STEP_BYTES = 128 * 1024
_LANES = 128


def _tiled(rows: int, lanes: int, it: int) -> int:
    """Bytes of a 2-D VMEM block in Mosaic's (8, 128) tiles."""
    return -(-rows // WINDOW) * WINDOW * -(-lanes // _LANES) * _LANES * it


# Each kernel DMAs its rows into a scratch that Mosaic lays out one
# sublane per row: ``(rows, 1, tile)`` takes ``rows · tile`` words, not
# an (8, 128) tile a row. The SpMM kernels then read the rows as one
# ``(rows, tile)`` value (``spmm_mxu``: stacked once per head for the
# dot) or as ``(8, tile)`` slices (``spmm_vpu``), and Mosaic keeps a
# relayout of them in VMEM too: up to 1.6× the scratch alone in those
# compiles, charged here as a second copy (a copy per head for the
# stacked operand). The SDDMM kernels need no such copy.
def spmm_mxu_step_bytes(vecs: int, nt: int, *, heads: int = 1,
                        it: int = 4) -> int:
    """One ``spmm_mxu`` grid step: the ``(8, H·vecs)`` value block and
    the ``(8, nt)`` output block, double-buffered, the ``(vecs, 1, nt)``
    fetched rows and the dot's ``(H·vecs, nt)`` operand."""
    return (2 * (_tiled(WINDOW, heads * vecs, it) + _tiled(WINDOW, nt, it))
            + (1 + heads) * vecs * nt * it + _MOSAIC_STEP_BYTES)


def spmm_vpu_step_bytes(els: int, nt: int, *, heads: int = 1,
                        it: int = 4) -> int:
    """One ``spmm_vpu`` grid step: 8 segments' ``(8, H·els)`` values and
    the ``(8, nt)`` output, double-buffered, and the ``(els, 8, 1, nt)``
    fetched rows with their relayout."""
    return (2 * (_tiled(WINDOW, heads * els, it) + _tiled(WINDOW, nt, it))
            + 2 * els * WINDOW * nt * it + _MOSAIC_STEP_BYTES)


def sddmm_mxu_step_bytes(vecs: int, kf: int, *, heads: int = 1,
                         it: int = 4) -> int:
    """One ``sddmm_mxu`` grid step: the ``(1, vecs)`` bitmap (a whole
    tile) and the ``(8·H, vecs)`` scores, double-buffered, the
    ``(8, kf)`` X window and the ``(vecs, 1, kf)`` fetched Y rows."""
    return (2 * (_tiled(1, vecs, it) + _tiled(WINDOW * heads, vecs, it))
            + _tiled(WINDOW, kf, it) + vecs * kf * it + _MOSAIC_STEP_BYTES)


def sddmm_vpu_step_bytes(els: int, kf: int, *, heads: int = 1,
                         it: int = 4) -> int:
    """One ``sddmm_vpu`` grid step: the ``(H·8, els)`` scores,
    double-buffered, and the fetched X and Y rows, ``(els, 8, 1, kf)``
    each."""
    return (2 * _tiled(WINDOW * heads, els, it)
            + 2 * els * WINDOW * kf * it + _MOSAIC_STEP_BYTES)


def vmem_spmm_bytes(cfg: TuneConfig, *, bk: int, ts: int,
                    dtype=np.float32, heads: int = 1) -> int:
    """Resident bytes of one pipelined grid step at lane tile
    ``cfg.nt`` and ``heads`` heads, max over the two SpMM kernels (the
    streams are scheduled independently).

    Both kernels keep B in HBM and DMA the rows a step's ids name into a
    VMEM scratch, so nothing here grows with ``k``. The ids sit in SMEM.
    ``ts`` here is the VPU *tile width* (``ts_tile``); the §4.3 segment
    caps (``cfg.ts``/``cfg.cs``) set the per-step widths this model
    charges for.
    """
    it = _itemsize(dtype)
    mxu_vecs, vpu_els = _seg_widths(cfg, bk=bk, ts_tile=ts)
    return max(spmm_mxu_step_bytes(mxu_vecs, cfg.nt, heads=heads, it=it),
               spmm_vpu_step_bytes(vpu_els, cfg.nt, heads=heads, it=it))


def vmem_sddmm_bytes(cfg: TuneConfig, *, bk: int, ts: int,
                     dtype=np.float32, heads: int = 1) -> int:
    """Resident bytes of one pipelined SDDMM grid step at feature tile
    ``cfg.kf_tile`` and ``heads`` heads (max over the two kernels).

    X and Y stay in HBM; a step DMAs the rows its ids name, one
    ``kf_tile`` feature slice at a time, so nothing here grows with the
    operand heights.
    """
    it = _itemsize(dtype)
    mxu_vecs, vpu_els = _seg_widths(cfg, bk=bk, ts_tile=ts)
    return max(sddmm_mxu_step_bytes(mxu_vecs, cfg.kf_tile, heads=heads,
                                    it=it),
               sddmm_vpu_step_bytes(vpu_els, cfg.kf_tile, heads=heads,
                                    it=it))


def occupancy_report(step_bytes: int,
                     budget: int = VMEM_BUDGET_BYTES) -> dict:
    """Pipeline-depth view of a footprint: how many grid steps' working
    sets fit in VMEM at once (≥ 2 ⇒ compute/DMA overlap is possible)."""
    return {
        "bytes_per_step": int(step_bytes),
        "budget_bytes": int(budget),
        "pipeline_depth": int(budget // max(step_bytes, 1)),
        "fits": bool(step_bytes <= budget),
    }


# ---------------------------------------------------- threshold model ---
def _modeled_spmm_time(feat: MatrixFeatures, threshold: int, *, n: int,
                       bk: int, hw: HardwareModel) -> float:
    """Roofline time of the hybrid split at ``threshold`` — same formulas
    as :func:`repro.core.threshold.model_spmm_time` but priced directly
    off the vector histogram (no plan construction per candidate)."""
    vec_ge = feat.vectors_at_least(threshold)
    nblk = int(np.ceil(vec_ge / bk).sum())
    tc_nnz = feat.nnz_at_least(threshold)
    vpu_nnz = feat.nnz - tc_nnz
    flops_mxu = 2.0 * nblk * WINDOW * bk * n
    bytes_mxu = 4.0 * nblk * bk * n + 4.0 * nblk * WINDOW * bk
    t_mxu = max(flops_mxu / (hw.mxu_tflops * 1e12),
                bytes_mxu / (hw.hbm_gbps * 1e9))
    flops_vpu = 2.0 * vpu_nnz * n
    bytes_vpu = 4.0 * vpu_nnz * n
    t_vpu = max(flops_vpu / (hw.vpu_tflops * 1e12),
                bytes_vpu / (hw.hbm_gbps * 1e9))
    return max(t_mxu, t_vpu) + 1e-12


def sddmm_window_split(feat: MatrixFeatures, threshold: int, bk: int
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-window SDDMM TC/VPU split approximation, shared by the cost
    model and the dist partitioner's segment curve (so shard balancing
    follows the same split the per-shard plans will use).

    SDDMM distributes at 8×bk-block granularity (densest-first packing):
    approximate each window's candidate blocks by packing its vectors
    densest-first and keeping blocks with ≥ ``threshold`` mean nnz on
    the MXU. Returns ``(tc_mask, nblk_w, nnz_w)`` per window.
    """
    hist = feat.win_vec_hist
    counts = np.arange(WINDOW + 1)
    nvec_w = hist.sum(axis=1)
    nnz_w = (hist * counts[None, :]).sum(axis=1)
    nblk_w = np.ceil(nvec_w / bk)
    with np.errstate(divide="ignore", invalid="ignore"):
        mean_blk_nnz = np.where(nblk_w > 0, nnz_w / np.maximum(nblk_w, 1), 0)
    return mean_blk_nnz >= threshold, nblk_w, nnz_w


def _modeled_sddmm_time(feat: MatrixFeatures, threshold: int, *, kf: int,
                        bk: int, hw: HardwareModel) -> float:
    """Roofline time of the SDDMM block split at ``threshold`` nnz/block
    (see :func:`sddmm_window_split` for the split approximation)."""
    tc_mask, nblk_w, nnz_w = sddmm_window_split(feat, threshold, bk)
    nblk = int(nblk_w[tc_mask].sum())
    tc_nnz = int(nnz_w[tc_mask].sum())
    vpu_nnz = feat.nnz - tc_nnz
    flops_mxu = 2.0 * nblk * WINDOW * bk * kf
    bytes_mxu = 4.0 * nblk * (WINDOW + bk) * kf
    t_mxu = max(flops_mxu / (hw.mxu_tflops * 1e12),
                bytes_mxu / (hw.hbm_gbps * 1e9))
    flops_vpu = 2.0 * vpu_nnz * kf
    bytes_vpu = 8.0 * vpu_nnz * kf
    t_vpu = max(flops_vpu / (hw.vpu_tflops * 1e12),
                bytes_vpu / (hw.hbm_gbps * 1e9))
    return max(t_mxu, t_vpu) + 1e-12


# ------------------------------------------------------------ tuners ---
def _pick_tile(fits, candidates):
    """The largest candidate that fits; the smallest when none does."""
    return next((c for c in candidates if fits(c)), candidates[-1])


_TS_SEG_CANDIDATES = (1, 2, 4, 8, 16, 32)
_SPT_CANDIDATES = (1, 2, 4, 8)   # VPU tiles per segment (cs / ts_tile)
# Grid-step overhead in units of one block/tile of work. Each step pays
# a fixed scheduling/DMA-issue cost on top of its payload; the cost of a
# cap is ``nseg·(overhead + cap)`` — padded work plus per-step overhead
# — so heavy owners merge (a window of ~8 real blocks becomes one step)
# while 1-unit owners keep cap 1 and never pad. Measured ≈ one
# block/tile of work per step on the interpret substrate.
_SEG_STEP_OVERHEAD = 1


def _pick_seg_ts(feat: MatrixFeatures, threshold: int | None,
                 bk: int) -> int:
    """§4.3 Ts cap from the blocks/window histogram: minimize the modeled
    MXU sweep cost ``nseg · (overhead + ts)``. A wide cap amortizes
    per-step overhead across decomposed (power-law) windows; a narrow one
    avoids padding 1-block windows up to the cap."""
    from repro.core.balance import BalanceParams

    vec_ge = feat.vectors_at_least(threshold or 1) \
        if feat.win_vec_hist.size else np.zeros(0, np.int64)
    blocks_w = -(-vec_ge // bk)
    blocks_w = blocks_w[blocks_w > 0]
    if blocks_w.size == 0:
        return BalanceParams().ts
    best, best_cost = _TS_SEG_CANDIDATES[0], None
    for ts in _TS_SEG_CANDIDATES:
        nseg = int(np.ceil(blocks_w / ts).sum())
        cost = nseg * (_SEG_STEP_OVERHEAD + ts)
        if best_cost is None or cost < best_cost:
            best, best_cost = ts, cost
    return best


def _pick_seg_cs(feat: MatrixFeatures, ts_tile: int) -> int:
    """§4.3 Cs cap (whole VPU tiles per row-segment) from the nnz/row
    histogram — residual rows are never longer than their source rows, so
    the row histogram upper-bounds tiles per row."""
    from repro.core.balance import BalanceParams

    rows = feat.row_hist[feat.row_hist > 0] if feat.row_hist.size \
        else np.zeros(0, np.int64)
    if rows.size == 0:
        return BalanceParams().cs
    tiles_r = np.ceil(rows / max(ts_tile, 1))
    best, best_cost = _SPT_CANDIDATES[0], None
    for spt in _SPT_CANDIDATES:
        nseg = int(np.ceil(tiles_r / spt).sum())
        cost = nseg * (_SEG_STEP_OVERHEAD + spt)
        if best_cost is None or cost < best_cost:
            best, best_cost = spt, cost
    return best * ts_tile


def _pick_ts_tile(feat: MatrixFeatures) -> int:
    """Residual-tile width from the nnz/row histogram: rows shorter than
    the tile waste padded lanes, so size the tile to the p95 row length
    (residual rows are never longer than their source row)."""
    if not feat.row_hist.size:
        return 32
    p95 = float(np.percentile(feat.row_hist, 95))
    return 8 if p95 <= 8 else 16 if p95 <= 16 else 32


def model_tune_spmm(a: SparseCSR, *, n: int = 128, dtype=np.float32,
                    bk: int | None = None, ts_tile: int | None = None,
                    mode: str = "hybrid",
                    threshold: int | None = None,
                    hw: HardwareModel = HardwareModel(),
                    budget: int = VMEM_BUDGET_BYTES,
                    feat: MatrixFeatures | None = None) -> TuneConfig:
    """Emit a full SpMM :class:`TuneConfig` from matrix features.

    Explicit ``threshold`` (or a forcing ``mode``) is respected — the
    model then only sizes the tile caps and segment caps. Explicit
    ``bk``/``ts_tile`` are likewise kept (and priced), so the emitted
    config always describes the plan that will actually be built.
    """
    from repro.core import preprocess as P
    from repro.obs.trace import get_tracer

    _sp = get_tracer().span("tune.model", op="spmm", m=a.m, k=a.k,
                            nnz=a.nnz).open()
    bk = P.DEFAULT_BK_SPMM if bk is None else bk
    feat = feat or matrix_features(a)
    ts_tile = _pick_ts_tile(feat) if ts_tile is None else ts_tile

    if threshold is None and mode == "hybrid":
        cand = range(1, WINDOW + 2)
        times = {t: _modeled_spmm_time(feat, t, n=n, bk=bk, hw=hw)
                 for t in cand}
        threshold = min(times, key=lambda t: (times[t], t))

    # §4.3 segment caps from the blocks/window and nnz/row histograms.
    seg_ts = _pick_seg_ts(feat, threshold, bk)
    seg_cs = _pick_seg_cs(feat, ts_tile)

    # Lane tile cap: the widest whose pipelined step fits the budget.
    # ``n`` prices the threshold only: each call takes the widest tile
    # under this cap that its own width allows (:func:`lane_tile`).
    nts = _NT_CANDIDATES

    def fits(nt):
        cfg = TuneConfig(nt=nt, ts=seg_ts, cs=seg_cs)
        return vmem_spmm_bytes(cfg, bk=bk, ts=ts_tile, dtype=dtype) <= budget

    nt = _pick_tile(fits, nts)
    # Still over budget at the narrowest tile ⇒ narrow the segment caps
    # before warning (a segment's fetched-rows scratch scales with
    # them), then re-pick: the narrowed caps may re-admit a wider tile.
    if not fits(nt):
        while not fits(nt) and seg_ts > 1:
            seg_ts //= 2
        while not fits(nt) and seg_cs > ts_tile:
            seg_cs //= 2
        nt = _pick_tile(fits, nts)

    cfg = TuneConfig(nt=nt, threshold=threshold, bk=bk,
                     ts_tile=ts_tile, ts=seg_ts, cs=seg_cs, source="model")
    step = vmem_spmm_bytes(cfg, bk=bk, ts=ts_tile, dtype=dtype)
    if step > budget:  # smallest candidates still don't fit
        warnings.warn(
            f"model_tune_spmm: smallest tile candidates need {step} B "
            f"per grid step, over the {budget} B VMEM budget",
            RuntimeWarning, stacklevel=2)
    _sp.set(threshold=threshold, nt=nt, vmem_step_bytes=step).close()
    return cfg


def model_tune_sddmm(a: SparseCSR, *, kf: int = 128, dtype=np.float32,
                     bk: int | None = None, ts_tile: int | None = None,
                     mode: str = "hybrid",
                     threshold: int | None = None,
                     hw: HardwareModel = HardwareModel(),
                     budget: int = VMEM_BUDGET_BYTES,
                     feat: MatrixFeatures | None = None) -> TuneConfig:
    """Emit a full SDDMM :class:`TuneConfig` from matrix features.

    Warns (RuntimeWarning) when even the smallest tile candidates exceed
    the budget (no footprint grows with the operands, so this only
    happens for pathological ``bk``/``ts_tile`` overrides).
    """
    from repro.core import preprocess as P
    from repro.obs.trace import get_tracer

    _sp = get_tracer().span("tune.model", op="sddmm", m=a.m, k=a.k,
                            nnz=a.nnz).open()
    bk = P.DEFAULT_BK_SDDMM if bk is None else bk
    feat = feat or matrix_features(a)
    ts_tile = 32 if ts_tile is None else ts_tile

    if threshold is None and mode == "hybrid":
        cand = (1, 8, 16, 24, 32, 48, 64, WINDOW * bk + 1)
        times = {t: _modeled_sddmm_time(feat, t, kf=kf, bk=bk, hw=hw)
                 for t in cand}
        threshold = min(times, key=lambda t: (times[t], t))

    # §4.3 segment caps (same histograms as SpMM; SDDMM VPU tiles are
    # flat element lists, so cs only batches tiles per grid step there).
    seg_ts = _pick_seg_ts(feat, 1, bk)
    seg_cs = _pick_seg_cs(feat, ts_tile)

    # Feature tile cap: the widest that fits (``kf`` prices the
    # threshold only; each call's tile follows its width).
    kfs = _KF_CANDIDATES

    def fits(kf_c):
        cfg = TuneConfig(kf_tile=kf_c, ts=seg_ts, cs=seg_cs)
        return vmem_sddmm_bytes(cfg, bk=bk, ts=ts_tile,
                                dtype=dtype) <= budget

    kf_tile = _pick_tile(fits, kfs)
    if not fits(kf_tile):
        while not fits(kf_tile) and seg_ts > 1:
            seg_ts //= 2
        while not fits(kf_tile) and seg_cs > ts_tile:
            seg_cs //= 2
        kf_tile = _pick_tile(fits, kfs)

    cfg = TuneConfig(kf_tile=kf_tile, threshold=threshold,
                     bk=bk, ts_tile=ts_tile, ts=seg_ts, cs=seg_cs,
                     source="model")
    step = vmem_sddmm_bytes(cfg, bk=bk, ts=ts_tile, dtype=dtype)
    if step > budget:
        warnings.warn(
            f"model_tune_sddmm: smallest tile candidates need {step} B "
            f"per grid step, over the {budget} B VMEM budget",
            RuntimeWarning, stacklevel=2)
    _sp.set(threshold=threshold, kf_tile=kf_tile,
            vmem_step_bytes=step).close()
    return cfg


# ------------------------------------------------------ per-call tile ---
def lane_tile(op: str, width: int, cfg: TuneConfig, *,
              heads: int | None = None) -> int:
    """The lane tile of one ``op`` call of ``width`` dense columns.

    Of the candidates (``_NT_CANDIDATES`` for ``op`` = ``"spmm"``,
    ``_KF_CANDIDATES`` for ``"sddmm"``) at most the plan's cap
    (``cfg.nt`` / ``cfg.kf_tile``) whose grid step at ``heads`` heads
    fits ``VMEM_BUDGET_BYTES``, the one that covers the width in the
    fewest tiles, and the narrowest of those, so no lanes are padded
    that a wider tile would not need. Every row copy of the kernels
    moves one tile of a row: a call makes ``ceil(width / tile)`` copies
    per real non-zero. Plain Python on static shapes: the applies call
    it at trace time.
    """
    from repro.core import preprocess as P

    spmm = op == "spmm"
    field, cap, cands, vmem, bk = (
        ("nt", cfg.nt, _NT_CANDIDATES, vmem_spmm_bytes, P.DEFAULT_BK_SPMM)
        if spmm else ("kf_tile", cfg.kf_tile, _KF_CANDIDATES,
                      vmem_sddmm_bytes, P.DEFAULT_BK_SDDMM))
    bk = cfg.bk or bk
    ts_tile = cfg.ts_tile or 32

    def fits(tile):
        return vmem(cfg.replace(**{field: tile}), bk=bk, ts=ts_tile,
                    heads=heads or 1) <= VMEM_BUDGET_BYTES

    tiles = [c for c in cands if c <= cap and fits(c)] or [cands[-1]]
    return min(tiles, key=lambda c: (-(-width // c), c))
