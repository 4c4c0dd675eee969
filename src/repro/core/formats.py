"""Device-side formats produced by Libra preprocessing.

Two storage families, mirroring the paper's bitmap (TC-block) + CSR split:

* :class:`TCBlocks` — the MXU ("Tensor-core") portion. Non-zero 8×1 column
  vectors whose NNZ passed the threshold, condensed into ``8 × BK`` blocks.
  Each condensed column keeps its source column index and an 8-bit occupancy
  bitmap (the paper's Bit-Decoding format). On TPU the values are stored as
  a dense VMEM-tileable ``(nblk, 8, BK)`` array — the bitmap is used for
  SDDMM sampling/write-back masks and for format size accounting.

* :class:`VPUTiles` — the CUDA-core portion, adapted to the TPU VPU. The
  residual non-zeros are packed into fixed-width tiles of ``TS`` elements,
  each tile owned by a single output row (SpMM) or a flat element list
  (SDDMM). Zero padding in a tile multiplies row 0 of B by 0.0 — harmless
  and branch-free.

Both carry segment/accumulation metadata from the hybrid load balancer
(paper §4.3): ``segment_id`` plays the role of the ``CurWindow/CurRow``
arrays and ``atomic`` marks partials that must be reduced (on TPU: summed
via deterministic segment reduction instead of atomicAdd).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

WINDOW = 8  # paper: 8×1 non-zero column vectors (swap-and-transpose granularity)

#: The two device-byte attribution views of a plan (see
#: :func:`view_of_key` / :class:`PlanArrays`): the §4.3 segment launch
#: tables every apply reads, and the revaluation position maps.
PLAN_VIEWS = ("segment", "revalue")

# SpMM value tensor → the canonical-nnz position map that rebuilds it
# (read only by ref.revalue_spmm_arrays). SDDMM's ``sddmm_src`` gather
# index is structural — every apply needs it, so it stays in "segment".
_REVALUE_OF = {"tc_seg_vals": "tc_seg_pos", "vpu_seg_vals": "vpu_seg_pos"}


def view_of_key(key: str) -> str:
    """Classify one device-array key into a :data:`PLAN_VIEWS` view."""
    return "revalue" if key in _REVALUE_OF.values() else "segment"


@dataclasses.dataclass(frozen=True)
class TCBlocks:
    """Condensed MXU blocks for one sparse matrix.

    vals:    (nblk, WINDOW, bk) f32 — condensed dense tiles (zero padded)
    cols:    (nblk, bk) i32 — source column index per condensed vector
    bitmap:  (nblk, bk) u32 — 8-bit occupancy of each 8×1 vector
    window:  (nblk,) i32 — output window (row-block) id of each block
    atomic:  (nblk,) bool — True if this window's output is also written by
             another path/segment and must go through the combine reduction
    nnz:     int — non-zeros covered by this portion

    Two fields are *derived* from ``window`` (the TC-window compaction map):

    rank:       (nblk,) i32 — dense rank of each block's window among the
                windows that have TC work (the slab a block sums into in
                :func:`repro.kernels.ref.spmm_tc_compact_ref`'s
                ``(n_active, 8, n)`` layout).
    active_win: (n_active,) i32 — rank → window id.
    """

    vals: np.ndarray
    cols: np.ndarray
    bitmap: np.ndarray
    window: np.ndarray
    atomic: np.ndarray
    nnz: int
    bk: int
    pos: np.ndarray | None = None  # (nblk, WINDOW, bk) canonical nnz idx, −1 pad
    rank: np.ndarray = dataclasses.field(init=False)
    active_win: np.ndarray = dataclasses.field(init=False)

    def __post_init__(self):
        # Preprocessing always emits ≥ 1 block (a zero dummy when the TC
        # portion is empty — see preprocess._pad_blocks), so active_win is
        # normally non-empty. A block-less TCBlocks keeps active_win empty
        # rather than fabricating a window with no backing block (which
        # would scatter an unwritten kernel output into C).
        win = np.asarray(self.window, np.int32)
        active = np.unique(win)
        object.__setattr__(self, "active_win", active.astype(np.int32))
        object.__setattr__(
            self, "rank", np.searchsorted(active, win).astype(np.int32))

    @property
    def nblk(self) -> int:
        return int(self.vals.shape[0])

    @property
    def n_active(self) -> int:
        return int(self.active_win.shape[0])

    @property
    def padded_zeros(self) -> int:
        return int(self.vals.size - self.nnz)


@dataclasses.dataclass(frozen=True)
class VPUTiles:
    """Residual-nonzero tiles for the VPU path (SpMM flavour).

    vals: (nt, ts) f32, cols: (nt, ts) i32, row: (nt,) i32 output row.
    long_tile: (nt,) bool — True for tiles from decomposed long rows
    (paper's long/short CUDA-core tile split; short tiles own their row
    exclusively and can store, long tiles must accumulate).
    """

    vals: np.ndarray
    cols: np.ndarray
    row: np.ndarray
    long_tile: np.ndarray
    atomic: np.ndarray
    nnz: int
    ts: int
    pos: np.ndarray | None = None  # (nt, ts) canonical nnz idx, −1 pad

    @property
    def ntiles(self) -> int:
        return int(self.vals.shape[0])


@dataclasses.dataclass(frozen=True)
class COOTiles:
    """Element tiles for the SDDMM VPU path: flat (row, col) element lists."""

    rows: np.ndarray  # (nt, ts) i32
    cols: np.ndarray  # (nt, ts) i32
    out_pos: np.ndarray  # (nt, ts) i32 — position in the canonical nnz array
    mask: np.ndarray  # (nt, ts) bool
    nnz: int
    ts: int

    @property
    def ntiles(self) -> int:
        return int(self.rows.shape[0])


@dataclasses.dataclass(frozen=True)
class SpMMPlan:
    """Full Libra plan for SpMM on one sparse matrix."""

    m: int
    k: int
    nnz: int
    threshold: int
    tc: TCBlocks
    vpu: VPUTiles
    meta: dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SDDMMPlan:
    """Full Libra plan for SDDMM on one sparse mask."""

    m: int
    k: int  # number of columns of the sparse mask (= rows of B)
    nnz: int
    threshold: int
    tc: TCBlocks  # vals unused (mask only); bitmap/cols/window are the block defs
    tc_out_pos: np.ndarray  # (nblk, WINDOW, bk) i32 → canonical nnz positions (-1 pad)
    vpu: COOTiles
    meta: dict[str, Any]


def _seg_take_map(seg, n_units: int) -> tuple[np.ndarray, np.ndarray]:
    """(take, mask) for one §4.3 segment table: ``take`` is ``(nseg,
    limit)`` indices into the owner-sorted unit array (clamped to valid
    units) and ``mask`` marks real units. Plans whose path is empty get
    one dummy all-padding segment so kernel shapes stay static (the
    exact analogue of the dummy zero TC block)."""
    from repro.core.balance import segment_take

    if seg.nseg == 0:
        take = np.full((1, max(seg.limit, 1)), -1, np.int64)
    else:
        take = segment_take(seg)
    mask = take >= 0
    return np.minimum(np.maximum(take, 0), max(n_units - 1, 0)), mask


def _spmm_segment_arrays(plan: "SpMMPlan") -> dict[str, np.ndarray]:
    """Segment-granular launch tables for the SpMM kernels (§4.3).

    MXU: segment ``s`` owns ≤ ``ts`` condensed blocks of one window,
    flattened to an ``(8, ts·bk)`` operand (the sum of per-block
    ``8×bk @ bk×n`` products equals one ``8×(ts·bk) @ (ts·bk)×n``
    product, so a segment is a single MXU dot); ``tc_seg_row`` maps its
    8 output rows to its window's rows of C. VPU: segment ``s`` owns
    ≤ ``cs`` residual elements (whole tiles) of one row — the same
    kernel, a wider tile; ``vpu_seg_len`` counts its real elements, so
    the kernel fetches no B row for its padding. With ``ts=1`` and
    ``cs=ts_tile`` the tables are the per-block/per-tile tensors
    themselves. Padding is inert: zero values, column 0, and ``pos`` −1
    so revaluation skips it.
    """
    out: dict[str, np.ndarray] = {}
    tc, tc_seg = plan.tc, plan.meta["tc_segments"]
    take, mask = _seg_take_map(tc_seg, tc.nblk)
    nseg, w = take.shape
    win = (tc_seg.cur if tc_seg.nseg else np.zeros(1, np.int64))
    vals = tc.vals[take] * mask[:, :, None, None]       # (nseg,w,8,bk)
    cols = np.where(mask[:, :, None], tc.cols[take], 0)
    bk = tc.vals.shape[-1]
    out["tc_seg_vals"] = vals.transpose(0, 2, 1, 3).reshape(
        nseg, WINDOW, w * bk).astype(np.float32)
    out["tc_seg_cols"] = cols.reshape(nseg, w * bk).astype(np.int32)
    pos = np.where(mask[:, :, None, None], tc.pos[take], -1)
    out["tc_seg_pos"] = pos.transpose(0, 2, 1, 3).reshape(
        nseg, WINDOW, w * bk).astype(np.int32)
    out["tc_seg_row"] = (
        win[:, None].astype(np.int64) * WINDOW
        + np.arange(WINDOW, dtype=np.int64)[None, :]
    ).reshape(-1).astype(np.int32)
    vpu, vpu_seg = plan.vpu, plan.meta["vpu_segments"]
    take, mask = _seg_take_map(vpu_seg, vpu.ntiles)
    nseg, spt = take.shape
    row = (vpu_seg.cur if vpu_seg.nseg else np.zeros(1, np.int64))
    ts = vpu.vals.shape[-1]
    out["vpu_seg_vals"] = (vpu.vals[take] * mask[:, :, None]).reshape(
        nseg, spt * ts).astype(np.float32)
    out["vpu_seg_cols"] = np.where(
        mask[:, :, None], vpu.cols[take], 0
    ).reshape(nseg, spt * ts).astype(np.int32)
    out["vpu_seg_pos"] = np.where(
        mask[:, :, None], vpu.pos[take], -1
    ).reshape(nseg, spt * ts).astype(np.int32)
    out["vpu_seg_row"] = row.astype(np.int32)
    out["vpu_seg_len"] = _vpu_seg_len(vpu, take, mask)
    return out


def _vpu_seg_len(vpu: VPUTiles, take: np.ndarray,
                 mask: np.ndarray) -> np.ndarray:
    """Real elements of each VPU segment, ``(nseg,)`` int32: the sum of
    its real tiles' fills. Preprocessing fills a row's tiles from slot 0
    and only the row's last tile is partial, so a segment's real
    elements are a prefix of its slots and the kernel fetches B rows for
    just that prefix. Fills come from the ``pos`` map (−1 on padding),
    never from the values: a real edge may hold 0."""
    fill = (vpu.pos >= 0).sum(axis=1)
    return (fill[take] * mask).sum(axis=1).astype(np.int32)


def spmm_vpu_seg_len(plan: "SpMMPlan") -> np.ndarray:
    """The ``vpu_seg_len`` table the VPU SpMM kernel launches with."""
    take, mask = _seg_take_map(plan.meta["vpu_segments"], plan.vpu.ntiles)
    return _vpu_seg_len(plan.vpu, take, mask)


def _sddmm_seg_tables(plan: "SDDMMPlan") -> dict[str, np.ndarray]:
    """Segment-granular host tables of the SDDMM kernels (§4.3).

    MXU: a segment's ≤ ``ts`` blocks share one window, so one grid step
    is a single ``8×kf @ kf×(ts·bk)`` score dot sampled by the
    concatenated bitmaps; ``tc_seg_out_pos`` names each scored cell's
    canonical non-zero (−1 on padding). VPU: element tiles are flat, so
    the Cs cap just batches ``seg_spt`` tiles per grid step
    (``vpu_seg_mask`` False on padding); at ``seg_spt`` 1 the tables are
    the per-tile tensors themselves. The out-position tables and the
    mask stay on the host: :func:`sddmm_gather_map` folds them into the
    combine's one device index.
    """
    out: dict[str, np.ndarray] = {}
    tc, tc_seg = plan.tc, plan.meta["tc_segments"]
    take, mask = _seg_take_map(tc_seg, tc.nblk)
    nseg, w = take.shape
    win = (tc_seg.cur if tc_seg.nseg else np.zeros(1, np.int64))
    bk = tc.cols.shape[-1]
    out["tc_seg_cols"] = np.where(
        mask[:, :, None], tc.cols[take], 0
    ).reshape(nseg, w * bk).astype(np.int32)
    out["tc_seg_bitmap"] = np.where(
        mask[:, :, None], tc.bitmap[take], 0
    ).reshape(nseg, w * bk).astype(np.uint32)
    out["tc_seg_window"] = win.astype(np.int32)
    out["tc_seg_out_pos"] = np.where(
        mask[:, :, None, None], plan.tc_out_pos[take], -1
    ).transpose(0, 2, 1, 3).reshape(nseg, WINDOW, w * bk).astype(np.int32)
    spt = int(plan.meta["seg_spt"])
    vpu = plan.vpu
    nt, ts = vpu.rows.shape
    nsegE = -(-nt // spt)
    pad = nsegE * spt - nt

    def _grp(x, fill):
        x = np.concatenate(
            [x, np.full((pad, ts), fill, x.dtype)]) if pad else x
        return x.reshape(nsegE, spt * ts)

    out["vpu_seg_rows"] = _grp(vpu.rows, 0).astype(np.int32)
    out["vpu_seg_cols"] = _grp(vpu.cols, 0).astype(np.int32)
    out["vpu_seg_out_pos"] = _grp(vpu.out_pos, 0).astype(np.int32)
    out["vpu_seg_mask"] = _grp(vpu.mask, False).astype(np.bool_)
    return out


def sddmm_gather_map(tc_out_pos: np.ndarray, vpu_out_pos: np.ndarray,
                     vpu_mask: np.ndarray, nnz: int,
                     length: int | None = None) -> np.ndarray:
    """The SDDMM combine's gather index, ``(length,)`` int32 (``length``
    defaults to ``nnz``): entry ``p`` is non-zero ``p``'s position in the
    kernels' scores laid end to end — the MXU stream's ``(nseg, WINDOW,
    w·bk)`` cells flattened, then the VPU stream's element slots. It is
    the inverse of the out-position tables, built once per plan: the
    build refuses a plan that does not place each of its ``nnz``
    non-zeros at exactly one position, and padding (out-pos −1, mask
    False) is never named. Entries from ``nnz`` to ``length`` (a stacked
    shard's padding non-zeros) read position 0."""
    pos = np.concatenate([np.asarray(tc_out_pos).reshape(-1),
                          np.where(vpu_mask, vpu_out_pos, -1).reshape(-1)])
    real = np.flatnonzero(pos >= 0)
    named = pos[real]
    counts = np.bincount(named, minlength=nnz)
    if named.size != nnz or counts.size != nnz or not (counts == 1).all():
        raise ValueError(
            f"SDDMM plan places {named.size} scores over {nnz} non-zeros: "
            "each non-zero needs exactly one position")
    src = np.zeros(nnz if length is None else length, np.int32)
    src[named] = real
    return src


def _sddmm_segment_arrays(plan: "SDDMMPlan") -> dict[str, np.ndarray]:
    """The SDDMM device tables: the kernels' segment tables
    (:func:`_sddmm_seg_tables`) and ``sddmm_src``, the combine's gather
    index (:func:`sddmm_gather_map`). Cap padding is never gathered:
    zero-bitmap MXU cells and mask-False VPU slots are scored and
    dropped."""
    out = _sddmm_seg_tables(plan)
    out["sddmm_src"] = sddmm_gather_map(
        out.pop("tc_seg_out_pos"), out.pop("vpu_seg_out_pos"),
        out.pop("vpu_seg_mask"), plan.nnz)
    return out


def sddmm_combine_slots(plan: "SDDMMPlan") -> int:
    """Length of the score buffer the SDDMM combine gathers from: the
    MXU segment table's cells (``nseg × WINDOW × ts·bk``) plus the VPU
    segment table's slots, the ``positions`` :func:`sddmm_gather_map`
    indexes."""
    seg = plan.meta["tc_segments"]
    nseg, w = (seg.nseg, seg.limit) if seg.nseg else (1, max(seg.limit, 1))
    spt = int(plan.meta["seg_spt"])
    nt, ts = plan.vpu.rows.shape
    return (nseg * w * WINDOW * plan.tc.cols.shape[-1]
            + -(-nt // spt) * spt * ts)


def _host_arrays(plan) -> dict[str, np.ndarray]:
    """Every device-uploadable array of one plan — its §4.3 segment
    tables and, for SpMM, their revaluation position maps, for SDDMM
    the combine's gather index — host-side,
    in its exact device dtype (so ``nbytes`` matches
    ``jax.Array.nbytes`` and a byte budget can be priced without
    uploading)."""
    if isinstance(plan, SpMMPlan):
        return _spmm_segment_arrays(plan)
    if isinstance(plan, SDDMMPlan):
        return _sddmm_segment_arrays(plan)
    raise TypeError(type(plan))  # pragma: no cover


class PlanArrays(Mapping):
    """Lazy, byte-accounted device views of one plan (paper §4.1 ③).

    ``PlanArrays`` keeps the plan host-side and uploads each array on
    first use:

    * :meth:`apply_arrays` returns the exact key set an apply reads —
      the same on both backends: the §4.3 segment tables, or with
      ``revalue=True`` the tables with each value tensor swapped for its
      position map, which :func:`repro.kernels.ref.revalue_spmm_arrays`
      rebuilds in-trace. A serving registry therefore never holds the
      baked-in values of a revalued plan.
    * Every upload is recorded (key, view, ``nbytes``, dtype); an
      *accountant* callback (:meth:`set_accountant` — usually a
      :class:`repro.obs.memstat.MemLedger` binder) receives each record,
      with already-resident uploads replayed on attach.
    * The object is a ``Mapping`` **and** a registered jax pytree whose
      flatten materializes every key — call sites that pass
      ``op.arrays`` straight into a jit (tests, benches, the GNN VJP)
      get every table and position map.
    """

    def __init__(self, plan):
        self.plan = plan
        self._host = _host_arrays(plan)
        self._views = {k: view_of_key(k) for k in self._host}
        self._dev: dict[str, jnp.ndarray] = {}
        self._uploads: dict[str, tuple[str, int, str]] = {}
        self._bcache: dict[bool, dict] = {}
        self._accountant = None

    # ------------------------------------------------------- mapping ---
    def __getitem__(self, key: str) -> jnp.ndarray:
        arr = self._dev.get(key)
        if arr is None:
            # First touch may happen inside a jit trace (legacy call
            # sites flatten op.arrays under tracing); force an eager
            # upload so the cached value is a concrete jax.Array, not
            # a tracer.
            with jax.ensure_compile_time_eval():
                arr = self._dev[key] = jnp.asarray(self._host[key])
            view = self._views[key]
            rec = (view, int(arr.nbytes), str(arr.dtype))
            self._uploads[key] = rec
            if self._accountant is not None:
                self._accountant(view, key, rec[1], rec[2])
        return arr

    def __iter__(self):
        return iter(self._host)

    def __len__(self) -> int:
        return len(self._host)

    def __contains__(self, key) -> bool:
        return key in self._host

    # --------------------------------------------------- apply views ---
    def apply_keys(self, *, revalue: bool = False) -> tuple[str, ...]:
        """The exact key set an apply of this plan reads (either
        backend); ``revalue`` swaps each value tensor for the position
        map that rebuilds it."""
        keys = [k for k in self._host if self._views[k] == "segment"]
        if revalue:
            keys = [_REVALUE_OF.get(k, k) for k in keys]
        return tuple(keys)

    def apply_arrays(self, *, revalue: bool = False
                     ) -> dict[str, jnp.ndarray]:
        """Materialize (upload on first use) and return the device dict
        of :meth:`apply_keys`; memoized per ``revalue``."""
        cached = self._bcache.get(revalue)
        if cached is None:
            cached = self._bcache[revalue] = {
                k: self[k] for k in self.apply_keys(revalue=revalue)}
        return cached

    def materialize_all(self) -> dict[str, jnp.ndarray]:
        """Upload every view and return the full device dict."""
        return {k: self[k] for k in self._host}

    # ---------------------------------------------------- accounting ---
    def set_accountant(self, accountant) -> None:
        """Attach a ``(view, key, nbytes, dtype) -> None`` upload
        recorder; uploads that already happened (e.g. during tune
        search) are replayed into it immediately."""
        self._accountant = accountant
        if accountant is not None:
            for key, (view, nbytes, dtype) in self._uploads.items():
                accountant(view, key, nbytes, dtype)

    def resident_items(self) -> list[tuple[str, jnp.ndarray]]:
        """The device arrays currently uploaded (ledger ground truth)."""
        return sorted(self._dev.items())

    def resident_nbytes(self, view: str | None = None) -> int:
        """Exact bytes resident on device (sum of uploaded
        ``jax.Array.nbytes``), optionally for one view."""
        return sum(nb for v, nb, _ in self._uploads.values()
                   if view is None or v == view)

    def view_nbytes(self) -> dict[str, int]:
        """Resident bytes per view (zero-filled over all views)."""
        out = {v: 0 for v in PLAN_VIEWS}
        for v, nb, _ in self._uploads.values():
            out[v] += nb
        return out

    def projected_nbytes(self, keys=None) -> int:
        """Bytes ``keys`` (every key when None) would hold resident once
        uploaded: their host ``nbytes`` (device dtypes match host — see
        :func:`_host_arrays`). No upload happens."""
        keys = self._host if keys is None else keys
        return sum(int(self._host[k].nbytes) for k in keys)

    def memory(self) -> dict:
        """Per-view resident/lazy breakdown for explain reports."""
        views: dict[str, dict] = {
            v: {"keys": 0, "resident_keys": 0, "bytes": 0,
                "resident_bytes": 0} for v in PLAN_VIEWS}
        for k, host in self._host.items():
            st = views[self._views[k]]
            st["keys"] += 1
            st["bytes"] += int(host.nbytes)
            rec = self._uploads.get(k)
            if rec is not None:
                st["resident_keys"] += 1
                st["resident_bytes"] += rec[1]
        return {
            "views": {v: st for v, st in views.items() if st["keys"]},
            "resident_bytes": self.resident_nbytes(),
            "total_bytes": sum(int(h.nbytes) for h in self._host.values()),
        }


def _plan_arrays_flatten(pa: PlanArrays):
    keys = tuple(sorted(pa._host))
    return tuple(pa[k] for k in keys), keys


def _plan_arrays_unflatten(keys, leaves) -> dict:
    # Reconstructing the lazy wrapper under tracing makes no sense —
    # flattened PlanArrays round-trip as the eager-equivalent dict.
    return dict(zip(keys, leaves))


jax.tree_util.register_pytree_node(
    PlanArrays, _plan_arrays_flatten, _plan_arrays_unflatten)


def device_arrays(plan) -> PlanArrays:
    """Lazy device views of a plan; arrays upload on first use and
    register their bytes (paper §4.1 ③ — upload once, reuse across
    iterations; see :class:`PlanArrays`)."""
    return PlanArrays(plan)
