"""Window-sharded partitioning of Libra plans (the distribution layer).

A :class:`~repro.sparse.matrix.SparseCSR` is split into ``P`` shards of
*contiguous 8-row windows* (the paper's SGT granularity — a window never
straddles shards, so every TC block and VPU tile lives wholly on one
device). Shard boundaries are chosen on the cumulative nnz curve, the
contiguous analogue of the hybrid balancer's segment decomposition:
per-shard nnz is within one window of the ideal ``nnz/P`` split
(:func:`repro.core.balance.balance_report` quantifies the residue in
``meta``).

Each shard is then a self-contained Libra problem:

* **column-halo compaction** — the shard's column indices are remapped
  onto the sorted-unique set of B/Y rows they touch (``Shard.halo``).
  The remap is monotone, so the shard's canonical CSR nnz order is
  exactly the global order restricted to its row range — value vectors
  slice, they never permute.
* **per-shard autotuning** — ``repro.tune`` runs on every shard's own
  pattern, so a dense-window shard and a hyper-sparse shard of the same
  matrix get different TC/VPU thresholds and tile sizes. Preprocessing
  consumes the per-shard config; the kernel-tile fields are combined
  conservatively (min across shards) into one ``run_cfg``, because a
  ``shard_map`` body is a single program. ``tune="search"`` builds the
  model-tuned partition: the ``run_cfg`` holds only lane-tile caps, a
  VMEM bound (each call derives its tile from its width), so a
  partition has nothing to time.
* **padded stacking** — per-shard §4.3 segment tables are padded to
  common shapes and stacked on a leading shard axis so ``shard_map``
  can split them over a mesh axis. Padding is *semantically inert by
  construction*: padding SpMM segments carry zero values and length 0
  and scatter onto the last local row (keeping every row map
  non-decreasing), padding SDDMM segments carry bitmap 0 / mask False
  and scatter into the swallow slot.

``out_gather`` / ``nnz_gather`` invert the padding: one global ``take``
reassembles the row-partitioned C (or the canonical nnz value vector)
from the stacked per-device outputs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.api import UNSET, ExecSpec, resolve_spec
from repro.core import preprocess
from repro.core.balance import BalanceParams, balance_report
from repro.core.formats import (
    WINDOW,
    _sddmm_segment_arrays,
    _spmm_segment_arrays,
)
from repro.core.sddmm import threshold_for_mode as sddmm_threshold_for_mode
from repro.core.spmm import threshold_for_mode as spmm_threshold_for_mode
from repro.core.windows import num_windows
from repro.obs.metrics import default_registry
from repro.sparse.matrix import SparseCSR
from repro.tune import TuneConfig, tune_sddmm, tune_spmm


def _publish_partition_gauges(op: str, meta: dict, n_shards: int) -> None:
    """Shard-balance gauges on the process metrics registry — the §4.3
    balance residue and halo overhead of the most recent partition of
    each operator, labeled by op."""
    m = default_registry()
    m.gauge("dist_shards", "Shard count of the last partition",
            labels=("op",)).set(n_shards, op=op)
    m.gauge("dist_nnz_max_over_mean",
            "nnz balance residue of the last partition",
            labels=("op",)).set(meta["balance"]["max_over_mean"], op=op)
    sb = meta.get("segment_balance")
    if sb:
        m.gauge("dist_segment_max_over_mean",
                "Segment-load balance residue of the last partition",
                labels=("op",)).set(sb["max_over_mean"], op=op)
    halo = sum(meta.get("halo_rows", []))
    nnz = max(sum(meta.get("shard_nnz", [])), 1)
    m.gauge("dist_halo_rows", "Total halo rows of the last partition",
            labels=("op",)).set(halo, op=op)
    m.gauge("dist_halo_waste_frac",
            "Halo rows / total nnz of the last partition",
            labels=("op",)).set(halo / nnz, op=op)


# ------------------------------------------------------- window split ---
def shard_windows(a: SparseCSR, n_shards: int,
                  weights: np.ndarray | None = None) -> np.ndarray:
    """Contiguous window ranges balanced on a per-window cost curve.

    Returns ``bounds`` of shape ``(n_shards + 1,)``: shard ``i`` owns
    windows ``[bounds[i], bounds[i+1])``. Boundaries sit where the
    cumulative cost curve crosses ``i · total/P``, so every shard's cost
    is within one window's cost of the ideal split (shards may be empty
    when ``P > nwin``). ``weights`` is the per-window cost (the
    partitioners pass the §4.3 *segment curve* — kernel grid steps, the
    quantity that actually bounds per-device latency on skewed
    matrices); ``None`` falls back to raw nnz.
    """
    nwin = num_windows(a.m)
    if weights is None:
        row_ends = np.minimum((np.arange(nwin) + 1) * WINDOW, a.m)
        cum = a.indptr[row_ends].astype(np.float64)  # nnz through window w
        total = float(a.nnz)
    else:
        weights = np.asarray(weights, np.float64)
        assert weights.shape == (nwin,), (weights.shape, nwin)
        cum = np.cumsum(weights)
        total = float(cum[-1]) if nwin else 0.0
    targets = total * (np.arange(1, n_shards) / n_shards)
    inner = np.searchsorted(cum, targets, side="left") + 1
    bounds = np.concatenate([[0], np.minimum(inner, nwin), [nwin]])
    return np.maximum.accumulate(bounds).astype(np.int64)


def segment_curve(a: SparseCSR, *, op: str, threshold: int, bk: int,
                  seg_ts: int, seg_cs: int, feat=None) -> np.ndarray:
    """Per-window §4.3 segment counts — the number of kernel grid steps
    (launch-table rows) each window contributes under the given caps.

    This is the curve the partitioners balance on: on power-law
    matrices, raw nnz under-weights windows whose work decomposes into
    many bounded segments (padding, per-step overhead), which is exactly
    where per-device latency skews. The VPU term lower-bounds segments
    by ``ceil(residual/cs)`` (row raggedness ignored — a balance
    heuristic, not a launch table). ``feat`` (a precomputed
    :func:`~repro.tune.model.matrix_features`) avoids a second full
    feature pass when the caller already tuned on the same matrix.
    """
    from repro.tune.model import matrix_features, sddmm_window_split

    feat = feat if feat is not None else matrix_features(a)
    hist = feat.win_vec_hist
    counts = np.arange(WINDOW + 1)
    nnz_w = (hist * counts[None, :]).sum(axis=1)
    if op == "spmm":
        t = int(np.clip(threshold, 1, WINDOW + 1))
        vec_tc_w = feat.vectors_at_least(threshold)
        tc_nnz_w = (hist[:, t:] * counts[None, t:]).sum(axis=1)
        blocks_w = -(-vec_tc_w // bk)
    else:  # sddmm: the cost model's block-granularity split, shared
        tc_mask, nblk_w, nnz_win = sddmm_window_split(feat, threshold, bk)
        blocks_w = np.where(tc_mask, nblk_w, 0).astype(np.int64)
        tc_nnz_w = np.where(tc_mask, nnz_win, 0)
    tc_segs = -(-blocks_w // seg_ts)
    vpu_segs = -(-(nnz_w - tc_nnz_w) // seg_cs)
    # matrix_features pads the histogram to max(nwin, 1) rows; trim so
    # an empty (m=0) matrix yields the empty curve shard_windows expects.
    return (tc_segs + vpu_segs).astype(np.int64)[:num_windows(a.m)]


def column_halo(a: SparseCSR, r0: int, r1: int
                ) -> tuple[np.ndarray, SparseCSR]:
    """Halo map + halo-remapped sub-CSR for global rows ``[r0, r1)``.

    The halo is the sorted-unique set of global B/Y-row ids the row
    range's column indices touch; the returned CSR has shape
    ``(r1 - r0, len(halo))`` with columns remapped onto halo positions.
    The remap is monotone (sorted halo), so canonical nnz order is
    preserved.
    """
    lo, hi = int(a.indptr[r0]), int(a.indptr[r1])
    cols = a.indices[lo:hi]
    halo = np.unique(cols).astype(np.int32)
    local_cols = np.searchsorted(halo, cols).astype(np.int32)
    indptr = (a.indptr[r0:r1 + 1] - lo).astype(np.int64)
    sub = SparseCSR(r1 - r0, max(int(halo.size), 1), indptr, local_cols,
                    a.data[lo:hi].astype(np.float32))
    return halo, sub


@dataclasses.dataclass(frozen=True)
class Shard:
    """One contiguous-window shard of a sparse matrix."""

    index: int
    win_start: int
    win_end: int
    row_start: int
    rows: int
    nnz_start: int
    nnz: int
    halo: np.ndarray     # (h,) i32 sorted unique global B/Y-row ids
    csr: SparseCSR       # (rows, max(h,1)) halo-remapped local matrix
    cfg: TuneConfig      # this shard's tuned plan-selection config


def _make_shards(a: SparseCSR, n_shards: int,
                 weights: np.ndarray | None = None) -> list[tuple]:
    bounds = shard_windows(a, n_shards, weights)
    out = []
    for p in range(n_shards):
        w0, w1 = int(bounds[p]), int(bounds[p + 1])
        r0 = min(w0 * WINDOW, a.m)
        r1 = max(min(w1 * WINDOW, a.m), r0)
        halo, sub = column_halo(a, r0, r1)
        out.append((p, w0, w1, r0, r1, halo, sub,
                    int(a.indptr[r0]), int(a.indptr[r1])))
    return out


def _combine_run_cfg(cfgs: list[TuneConfig], bk, ts_tile,
                     seg_ts, seg_cs) -> TuneConfig:
    """One kernel-tile config every shard can run: min tiles across
    shards (VMEM-safe on all of them). The
    §4.3 segment caps ride through verbatim — they are unified across
    shards before preprocessing (stacked launch tables must agree in
    width), like ``bk``/``ts_tile``."""
    return TuneConfig(
        nt=min(c.nt for c in cfgs),
        kf_tile=min(c.kf_tile for c in cfgs),
        threshold=None, bk=bk, ts_tile=ts_tile,
        ts=seg_ts, cs=seg_cs, source="dist",
    )


def _offset_pos(pos: np.ndarray, off: int) -> np.ndarray:
    """Shift shard-local canonical nnz positions to global (−1 stays)."""
    return np.where(pos >= 0, pos + off, -1).astype(np.int32)


def _stack_halos(shards) -> np.ndarray:
    """Each shard's halo map on a leading shard axis, zero-padded to the
    widest."""
    halo = np.zeros((len(shards), max(1, max(s.halo.size for s in shards))),
                    np.int32)
    for p, shard in enumerate(shards):
        halo[p, :shard.halo.size] = shard.halo
    return halo


def _stack_spmm_segments(plans, shards, n_shards,
                         pad_row: int) -> dict[str, np.ndarray]:
    """Pad/stack each shard's §4.3 segment launch tables on the leading
    shard axis. Padding segments are inert: zero values scatter zeros
    onto local row ``pad_row`` (the last, so rows stay non-decreasing),
    pos −1 skips revaluation and length 0 fetches no B row."""
    seg_list = [_spmm_segment_arrays(p) for p in plans]
    ns = max(s["tc_seg_vals"].shape[0] for s in seg_list)
    wbk = seg_list[0]["tc_seg_vals"].shape[-1]
    vals = np.zeros((n_shards, ns, WINDOW, wbk), np.float32)
    cols = np.zeros((n_shards, ns, wbk), np.int32)
    pos = np.full((n_shards, ns, WINDOW, wbk), -1, np.int32)
    row = np.full((n_shards, ns * WINDOW), pad_row, np.int32)
    for p, (s, sh) in enumerate(zip(seg_list, shards)):
        k = s["tc_seg_vals"].shape[0]
        vals[p, :k] = s["tc_seg_vals"]
        cols[p, :k] = s["tc_seg_cols"]
        pos[p, :k] = _offset_pos(s["tc_seg_pos"], sh.nnz_start)
        row[p, :k * WINDOW] = s["tc_seg_row"]
    out = dict(tc_seg_vals=vals, tc_seg_cols=cols, tc_seg_pos=pos,
               tc_seg_row=row)
    ns = max(s["vpu_seg_row"].shape[0] for s in seg_list)
    w = seg_list[0]["vpu_seg_vals"].shape[-1]
    vals = np.zeros((n_shards, ns, w), np.float32)
    cols = np.zeros((n_shards, ns, w), np.int32)
    pos = np.full((n_shards, ns, w), -1, np.int32)
    row = np.full((n_shards, ns), pad_row, np.int32)
    seg_len = np.zeros((n_shards, ns), np.int32)
    for p, (s, sh) in enumerate(zip(seg_list, shards)):
        k = s["vpu_seg_row"].shape[0]
        vals[p, :k] = s["vpu_seg_vals"]
        cols[p, :k] = s["vpu_seg_cols"]
        pos[p, :k] = _offset_pos(s["vpu_seg_pos"], sh.nnz_start)
        row[p, :k] = s["vpu_seg_row"]
        seg_len[p, :k] = s["vpu_seg_len"]
    out.update(vpu_seg_vals=vals, vpu_seg_cols=cols, vpu_seg_pos=pos,
               vpu_seg_row=row, vpu_seg_len=seg_len)
    return out


def _segment_load_meta(plans) -> dict[str, Any]:
    """Per-shard §4.3 segment counts (= kernel grid steps) — the load
    the segment-curve split balances."""
    def nseg(p):
        n = p.meta["tc_segments"].nseg
        vpu = p.meta["vpu_segments"]
        if vpu is None:  # SDDMM: flat element tiles grouped by seg_spt
            return n + -(-p.vpu.ntiles // int(p.meta["seg_spt"]))
        return n + vpu.nseg

    per = [nseg(p) for p in plans]
    mean = max(sum(per) / max(len(per), 1), 1e-9)
    return {"shard_segments": per,
            "segment_balance": {"max_over_mean": max(per) / mean,
                                "shards": len(per)}}


# ----------------------------------------------------------- partitions ---
@dataclasses.dataclass(frozen=True)
class SpMMPartition:
    """Window-sharded SpMM execution plan for one sparse matrix."""

    m: int
    k: int
    nnz: int
    n_shards: int
    shards: list[Shard]
    stacked: dict[str, jnp.ndarray]  # (P, ...) leading shard axis (+halo)
    wmax: int                        # windows per shard, padded
    rows_pad: int                    # = wmax * WINDOW, local C height
    run_cfg: TuneConfig              # kernel tiles every shard can run
    out_gather: jnp.ndarray          # (m,) stacked-row id of global row
    meta: dict[str, Any]
    reorder: Any = None              # repro.reorder.Reordering | None
    edge_perm: jnp.ndarray | None = None  # eff pos → original nnz pos


def partition_spmm(a: SparseCSR, n_shards: int, *, mode=UNSET,
                   threshold=UNSET, tune=UNSET, bk=UNSET, ts_tile=UNSET,
                   tune_n=UNSET, tune_cache=UNSET, tune_backend=UNSET,
                   spec: ExecSpec | None = None) -> SpMMPartition:
    """Split + per-shard tune + preprocess + pad/stack for sharded SpMM.

    Execution knobs live on one :class:`repro.api.ExecSpec` (``spec=``;
    the legacy kwargs keep working through the deprecation shim).
    ``spec.tune`` accepts ``"model"``/``"search"``/``"off"``/a
    :class:`TuneConfig`; ``"search"`` tunes as ``"model"`` (the module
    docstring says why).
    ``bk``/``ts_tile`` are unified across shards (stacked block shapes
    must agree); each shard still gets its own threshold and tiles.

    ``spec.reorder`` prices/applies the sparsity-aware row permutation
    on the *full* matrix before sharding, so shard boundaries balance
    the reordered segment curve. The composition is free at runtime:
    ``out_gather`` is pre-composed with the inverse row permutation
    (outputs come back in original row order) and ``edge_perm`` records
    the one extra gather sharded revaluation needs.
    """
    spec = resolve_spec(spec, "partition_spmm", mode=mode,
                        threshold=threshold, tune=tune, bk=bk,
                        ts_tile=ts_tile, tune_n=tune_n,
                        tune_cache=tune_cache, tune_backend=tune_backend)
    mode, threshold, tune = spec.mode, spec.threshold, spec.tune
    bk, ts_tile = spec.bk, spec.ts_tile
    tune_n = spec.tune_n
    tune = "model" if tune == "search" else tune
    # One global feature pass fixes the common block geometry (shared by
    # the base tune and the segment curve — no second O(nnz) pass).
    from repro.tune.model import matrix_features

    feat = matrix_features(a)
    forced = (spmm_threshold_for_mode(mode, threshold)
              if mode != "hybrid" else threshold)
    guess = preprocess.DEFAULT_SPMM_THRESHOLD if forced is None else forced
    a, reord, re_report, feat = preprocess._maybe_reorder(
        a, op="spmm", spec=spec, threshold=guess, feat=feat)
    base = tune_spmm(a, mode=mode, threshold=threshold, tune=tune,
                     n=tune_n, bk=bk, ts_tile=ts_tile, feat=feat)
    bk_c = bk if bk is not None else (base.bk or preprocess.DEFAULT_BK_SPMM)
    ts_c = ts_tile if ts_tile is not None else (base.ts_tile or 32)
    # §4.3 segment caps are unified like bk/ts_tile: stacked launch
    # tables must agree in width across shards.
    seg_ts = base.ts if base.ts is not None else BalanceParams.ts
    seg_cs = base.cs if base.cs is not None else BalanceParams.cs
    curve = segment_curve(
        a, op="spmm", threshold=spmm_threshold_for_mode(
            mode, forced if forced is not None else base.threshold),
        bk=bk_c, seg_ts=seg_ts, seg_cs=seg_cs, feat=feat)
    raw = _make_shards(a, n_shards, weights=curve)
    shards, plans = [], []
    for p, w0, w1, r0, r1, halo, sub, nz0, nz1 in raw:
        cfg = tune_spmm(sub, mode=mode, threshold=forced, tune=tune,
                        n=tune_n, bk=bk_c, ts_tile=ts_c)
        cfg = cfg.replace(ts=seg_ts, cs=seg_cs)
        thr = spmm_threshold_for_mode(mode, cfg.threshold)
        plan = preprocess.preprocess_spmm(sub, thr, cfg=cfg)
        shards.append(Shard(p, w0, w1, r0, r1 - r0, nz0, nz1 - nz0,
                            halo, sub, cfg))
        plans.append(plan)

    wmax = max(1, max(s.win_end - s.win_start for s in shards))
    rows_pad = wmax * WINDOW
    out_gather = np.zeros(a.m, np.int32)
    for shard in shards:
        rr = np.arange(shard.rows)
        out_gather[shard.row_start + rr] = shard.index * rows_pad + rr
    if reord is not None:
        # Compose the unpermute into the existing reassembly gather:
        # original row j lives at reordered row row_inv[j]. Zero extra
        # runtime cost — same single take as before.
        out_gather = out_gather[reord.row_inv]

    host = dict(halo=_stack_halos(shards))
    host.update(_stack_spmm_segments(plans, shards, n_shards, rows_pad - 1))
    stacked = {k: jnp.asarray(v) for k, v in host.items()}
    meta = {
        "balance": balance_report(
            np.asarray([s.nnz for s in shards], np.int64), n_shards),
        "halo_rows": [int(s.halo.size) for s in shards],
        "shard_nnz": [s.nnz for s in shards],
        "mode": mode,
        "reorder": re_report,
        **_segment_load_meta(plans),
    }
    _publish_partition_gauges("spmm", meta, n_shards)
    return SpMMPartition(a.m, a.k, a.nnz, n_shards, shards, stacked,
                         wmax, rows_pad,
                         _combine_run_cfg([s.cfg for s in shards], bk_c,
                                          ts_c, seg_ts, seg_cs),
                         jnp.asarray(out_gather), meta,
                         reorder=reord,
                         edge_perm=(None if reord is None
                                    else jnp.asarray(reord.nnz_perm)))


def _stack_sddmm_segments(plans, n_shards) -> dict[str, np.ndarray]:
    """SDDMM flavour of :func:`_stack_spmm_segments`. Out-positions stay
    shard-local (the scatter targets the local nnz slice; ``nnz_gather``
    reassembles) — padding carries bitmap 0 / mask False and pos −1/0,
    which the swallow slot absorbs."""
    seg_list = [_sddmm_segment_arrays(p) for p in plans]
    ns = max(s["tc_seg_window"].shape[0] for s in seg_list)
    wbk = seg_list[0]["tc_seg_cols"].shape[-1]
    cols = np.zeros((n_shards, ns, wbk), np.int32)
    bitmap = np.zeros((n_shards, ns, wbk), np.uint32)
    win = np.zeros((n_shards, ns), np.int32)
    opos = np.full((n_shards, ns, WINDOW, wbk), -1, np.int32)
    for p, s in enumerate(seg_list):
        k = s["tc_seg_window"].shape[0]
        cols[p, :k] = s["tc_seg_cols"]
        bitmap[p, :k] = s["tc_seg_bitmap"]
        win[p, :k] = s["tc_seg_window"]
        opos[p, :k] = s["tc_seg_out_pos"]
    out = dict(tc_seg_cols=cols, tc_seg_bitmap=bitmap, tc_seg_window=win,
               tc_seg_out_pos=opos)
    ns = max(s["vpu_seg_rows"].shape[0] for s in seg_list)
    w = seg_list[0]["vpu_seg_rows"].shape[-1]
    rows = np.zeros((n_shards, ns, w), np.int32)
    cols = np.zeros((n_shards, ns, w), np.int32)
    opos = np.zeros((n_shards, ns, w), np.int32)
    mask = np.zeros((n_shards, ns, w), bool)
    for p, s in enumerate(seg_list):
        k = s["vpu_seg_rows"].shape[0]
        rows[p, :k] = s["vpu_seg_rows"]
        cols[p, :k] = s["vpu_seg_cols"]
        opos[p, :k] = s["vpu_seg_out_pos"]
        mask[p, :k] = s["vpu_seg_mask"]
    out.update(vpu_seg_rows=rows, vpu_seg_cols=cols, vpu_seg_out_pos=opos,
               vpu_seg_mask=mask)
    return out


@dataclasses.dataclass(frozen=True)
class SDDMMPartition:
    """Window-sharded SDDMM execution plan for one sparse mask."""

    m: int
    k: int
    nnz: int
    n_shards: int
    shards: list[Shard]
    stacked: dict[str, jnp.ndarray]
    wmax: int
    rows_pad: int
    nnz_pad: int                     # local padded nnz per shard
    run_cfg: TuneConfig
    x_take: jnp.ndarray              # (P*rows_pad,) global X row per slot
    nnz_gather: jnp.ndarray          # (nnz,) stacked slot of global nnz p
    meta: dict[str, Any]
    reorder: Any = None              # repro.reorder.Reordering | None


def partition_sddmm(a: SparseCSR, n_shards: int, *, mode=UNSET,
                    threshold=UNSET, tune=UNSET, bk=UNSET, ts_tile=UNSET,
                    tune_kf=UNSET, tune_cache=UNSET, tune_backend=UNSET,
                    spec: ExecSpec | None = None) -> SDDMMPartition:
    """SDDMM flavour of :func:`partition_spmm` (same sharding geometry;
    scores come back in canonical global nnz order via ``nnz_gather``;
    same ``tune="search"`` and ``spec.reorder`` semantics — the legacy
    ``threshold=`` kwarg maps to ``ExecSpec.sddmm_threshold``). Under reordering, ``x_take`` is
    pre-composed with the row permutation and ``nnz_gather`` with the
    inverse nnz permutation, so X arrives and scores return in original
    order at zero extra runtime cost."""
    spec = resolve_spec(spec, "partition_sddmm", mode=mode,
                        sddmm_threshold=threshold, tune=tune, bk=bk,
                        ts_tile=ts_tile, tune_kf=tune_kf,
                        tune_cache=tune_cache, tune_backend=tune_backend)
    mode, threshold, tune = spec.mode, spec.sddmm_threshold, spec.tune
    bk, ts_tile = spec.bk, spec.ts_tile
    tune_kf = spec.tune_kf
    tune = "model" if tune == "search" else tune
    from repro.tune.model import matrix_features

    feat = matrix_features(a)
    bk_eff = preprocess.DEFAULT_BK_SDDMM if bk is None else bk
    forced0 = (sddmm_threshold_for_mode(mode, bk_eff, threshold)
               if mode != "hybrid" else threshold)
    guess = preprocess.DEFAULT_SDDMM_THRESHOLD if forced0 is None else forced0
    a, reord, re_report, feat = preprocess._maybe_reorder(
        a, op="sddmm", spec=spec, threshold=guess, feat=feat)
    base = tune_sddmm(a, mode=mode, threshold=threshold, tune=tune,
                      kf=tune_kf, bk=bk, ts_tile=ts_tile, feat=feat)
    bk_c = bk if bk is not None else (base.bk or preprocess.DEFAULT_BK_SDDMM)
    ts_c = ts_tile if ts_tile is not None else (base.ts_tile or 32)
    seg_ts = base.ts if base.ts is not None else BalanceParams.ts
    seg_cs = base.cs if base.cs is not None else BalanceParams.cs

    forced = (sddmm_threshold_for_mode(mode, bk_c, threshold)
              if mode != "hybrid" else threshold)
    curve = segment_curve(
        a, op="sddmm", threshold=sddmm_threshold_for_mode(
            mode, bk_c, forced if forced is not None else base.threshold),
        bk=bk_c, seg_ts=seg_ts, seg_cs=seg_cs, feat=feat)
    raw = _make_shards(a, n_shards, weights=curve)
    shards, plans = [], []
    for p, w0, w1, r0, r1, halo, sub, nz0, nz1 in raw:
        cfg = tune_sddmm(sub, mode=mode, threshold=forced, tune=tune,
                         kf=tune_kf, bk=bk_c, ts_tile=ts_c)
        cfg = cfg.replace(ts=seg_ts, cs=seg_cs)
        thr = sddmm_threshold_for_mode(mode, bk_c, cfg.threshold)
        plan = preprocess.preprocess_sddmm(sub, thr, cfg=cfg)
        shards.append(Shard(p, w0, w1, r0, r1 - r0, nz0, nz1 - nz0,
                            halo, sub, cfg))
        plans.append(plan)

    wmax = max(1, max(s.win_end - s.win_start for s in shards))
    rows_pad = wmax * WINDOW
    nnz_pad = max(1, max(s.nnz for s in shards))

    x_take = np.zeros(n_shards * rows_pad, np.int32)
    nnz_gather = np.zeros(a.nnz, np.int32)
    for shard in shards:
        sl = slice(shard.index * rows_pad, (shard.index + 1) * rows_pad)
        x_take[sl] = np.clip(shard.row_start + np.arange(rows_pad),
                             0, max(a.m - 1, 0))
        nnz_gather[shard.nnz_start:shard.nnz_start + shard.nnz] = \
            shard.index * nnz_pad + np.arange(shard.nnz)
    if reord is not None:
        # Compose the un-reorder into the existing gathers: X slots name
        # original rows directly (eff row i = original row row_perm[i]),
        # and original nnz p sits at reordered position nnz_inv[p].
        x_take = reord.row_perm.astype(np.int32)[x_take]
        nnz_gather = nnz_gather[reord.nnz_inv]

    host = dict(halo=_stack_halos(shards))
    host.update(_stack_sddmm_segments(plans, n_shards))
    stacked = {k: jnp.asarray(v) for k, v in host.items()}
    meta = {
        "balance": balance_report(
            np.asarray([s.nnz for s in shards], np.int64), n_shards),
        "halo_rows": [int(s.halo.size) for s in shards],
        "shard_nnz": [s.nnz for s in shards],
        "mode": mode,
        "reorder": re_report,
        **_segment_load_meta(plans),
    }
    _publish_partition_gauges("sddmm", meta, n_shards)
    return SDDMMPartition(a.m, a.k, a.nnz, n_shards, shards, stacked,
                          wmax, rows_pad, nnz_pad,
                          _combine_run_cfg([s.cfg for s in shards],
                                           bk_c, ts_c, seg_ts, seg_cs),
                          jnp.asarray(x_take), jnp.asarray(nnz_gather), meta,
                          reorder=reord)
