"""Pure-jnp oracles for every Pallas kernel (and XLA fallback paths).

These define the semantics the kernels must reproduce exactly (allclose):

* TC/MXU SpMM path: per condensed block ``P = vals @ B[cols]`` accumulated
  into the block's output window.
* VPU SpMM path: per tile ``p = Σ_j vals[j] · B[cols[j]]`` accumulated into
  the tile's output row.
* TC/MXU SDDMM path: per block ``S = X[win] @ Y[cols]ᵀ`` sampled by bitmap.
* VPU SDDMM path: per element ``s = ⟨X[row], Y[col]⟩``.

The hybrid references (:func:`spmm_hybrid_ref`, :func:`sddmm_hybrid_ref`)
run these semantics on the kernels' own §4.3 segment tables — a segment
is a block (or tile) of ``ts·bk`` (or ``cs``) columns — and serve as
the fast XLA backend on CPU (interpret-mode Pallas is a correctness
tool, not a CPU performance path).

Multi-head: edge values of shape ``(nnz, H)`` against dense operands
whose ``H·c`` columns hold the heads contiguously (head ``h`` owns
columns ``[h·c, (h+1)·c)``). Plan value tensors then carry a trailing
head axis; SpMM scales head ``h``'s columns by its value, SDDMM returns
one score per head, ``(..., H)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import WINDOW


def _tc_partials(tc_vals, gathered):
    """Per-block ``8 × n`` partials ``vals @ B[cols]`` from the gathered
    rows ``(nb, bk, n)``; values ``(nb, 8, bk)`` or, with a head axis,
    ``(nb, 8, bk, H)`` (each head's columns apart)."""
    if tc_vals.ndim == 3:
        return jnp.einsum("bsk,bkn->bsn", tc_vals, gathered)
    nb, bk, n = gathered.shape
    gathered = gathered.reshape(nb, bk, tc_vals.shape[3], -1)
    return jnp.einsum("bskh,bkhc->bshc", tc_vals,
                      gathered).reshape(nb, WINDOW, n)


def spmm_tc_compact_ref(tc_vals, tc_cols, tc_rank, b, n_active):
    """Compacted-layout oracle for :func:`repro.kernels.spmm_mxu.spmm_mxu`:
    ``(n_active*8, n)`` — one 8-row slab per TC-*active* window rank."""
    partial = _tc_partials(tc_vals, jnp.take(b, tc_cols, axis=0))
    out = jax.ops.segment_sum(partial, tc_rank, num_segments=n_active,
                              indices_are_sorted=True)
    return out.reshape(n_active * WINDOW, b.shape[1])


def _vpu_partials(vpu_vals, gathered):
    """Per-tile partial rows ``Σ_j vals[j] · B[cols[j]]`` from the
    gathered rows ``(nt, ts, n)``; values ``(nt, ts)`` or, with a head
    axis, ``(nt, ts, H)``."""
    if vpu_vals.ndim == 2:
        return jnp.einsum("tj,tjn->tn", vpu_vals, gathered)
    nt, ts, n = gathered.shape
    gathered = gathered.reshape(nt, ts, vpu_vals.shape[2], -1)
    return jnp.einsum("tjh,tjhc->thc", vpu_vals, gathered).reshape(nt, n)


def spmm_hybrid_ref(arrs, b, m, nwin):
    """Single-pass hybrid reference on the segment tables, mirroring
    the fused Pallas epilogue: each MXU segment's ``8 × n`` partial sums
    into its window of C, then the VPU segments' partial rows
    scatter-add over it. Segment windows and rows are non-decreasing in
    every plan, so both sums are sorted ones."""
    tc = _tc_partials(arrs["tc_seg_vals"],
                      jnp.take(b, arrs["tc_seg_cols"], axis=0))
    partials = _vpu_partials(arrs["vpu_seg_vals"],
                             jnp.take(b, arrs["vpu_seg_cols"], axis=0))
    tc_win = arrs["tc_seg_row"][::WINDOW] // WINDOW
    out = jax.ops.segment_sum(tc, tc_win, num_segments=nwin,
                              indices_are_sorted=True)
    out = out.reshape(nwin * WINDOW, b.shape[1])
    return out.at[arrs["vpu_seg_row"]].add(partials,
                                           indices_are_sorted=True)[:m]


def bitmap_mask(bitmap):
    """(..., bk) uint32 → (..., 8, bk) bool, bit r of column j ⇒ sublane r.

    The TPU-native Bit-Decoding: every sublane tests its own bit of the
    same 32-bit word (paper Fig. 8's ``(binary >> tid) & 1``).
    """
    sub = jnp.arange(WINDOW, dtype=jnp.uint32).reshape(
        (1,) * (bitmap.ndim - 1) + (WINDOW, 1)
    )
    bits = (bitmap[..., None, :] >> sub) & jnp.uint32(1)
    return bits.astype(jnp.bool_)


def sddmm_tc_ref(tc_cols, tc_bitmap, tc_window, x, y, heads=None):
    """Block scores: (nb, 8, bk) = X[window] · Y[cols]ᵀ masked by bitmap;
    (nb, 8, bk, H) per head with ``heads``."""
    nb = tc_cols.shape[0]
    xwin = jnp.take(
        x.reshape(-1, WINDOW, x.shape[-1]), tc_window, axis=0
    )  # (nb, 8, kf)
    yg = jnp.take(y, tc_cols, axis=0)  # (nb, bk, kf)
    if heads:
        s = jnp.einsum("bshc,bjhc->bsjh",
                       xwin.reshape(nb, WINDOW, heads, -1),
                       yg.reshape(nb, yg.shape[1], heads, -1))
        return jnp.where(bitmap_mask(tc_bitmap)[..., None], s, 0.0)
    s = jnp.einsum("bsk,bjk->bsj", xwin, yg)  # (nb, 8, bk)
    return jnp.where(bitmap_mask(tc_bitmap), s, 0.0)


def sddmm_vpu_ref(rows, cols, x, y, heads=None):
    """Element scores: (nt, ts) = ⟨X[row], Y[col]⟩; (nt, ts, H) per head
    with ``heads``. Padding slots score row 0 against column 0, which
    the combine never gathers."""
    xg = jnp.take(x, rows, axis=0)  # (nt, ts, kf)
    yg = jnp.take(y, cols, axis=0)
    if heads:
        return jnp.einsum("tjhc,tjhc->tjh",
                          xg.reshape(*rows.shape, heads, -1),
                          yg.reshape(*cols.shape, heads, -1))
    return jnp.einsum("tjk,tjk->tj", xg, yg)


def sddmm_hybrid_ref(arrs, x, y, heads=None):
    """Hybrid SDDMM on the segment tables, producing the canonical
    nnz-ordered value vector, ``(nnz, H)`` with ``heads``: one gather of
    both streams' scores by the plan's inverse position map
    (``sddmm_src``), as the Pallas combine does."""
    s_tc = sddmm_tc_ref(arrs["tc_seg_cols"], arrs["tc_seg_bitmap"],
                        arrs["tc_seg_window"], x, y, heads)
    s_el = sddmm_vpu_ref(arrs["vpu_seg_rows"], arrs["vpu_seg_cols"], x, y,
                         heads)
    tail = (heads,) if heads else ()
    data = jnp.concatenate([s_tc.reshape((-1,) + tail),
                            s_el.reshape((-1,) + tail)])
    return jnp.take(data, arrs["sddmm_src"], axis=0, mode="clip")


def revalue_spmm_arrays(arrs, edge_vals):
    """Rebuild a plan's segment value tensors from a runtime per-edge
    value vector.

    The sparsity pattern (and hence the whole Libra plan) is fixed; only
    values change — e.g. GNN attention weights per step. ``edge_vals``
    follows canonical CSR nnz order: ``(nnz,)``, or ``(nnz, H)`` with one
    value per head, which gives each value tensor a trailing head axis.
    The position maps are −1 on padding, which reads as 0.
    """
    def from_pos(pos):
        if edge_vals.ndim == 2:
            return jnp.where(
                (pos >= 0)[..., None],
                jnp.take(edge_vals, jnp.maximum(pos, 0), axis=0), 0.0
            ).astype(jnp.float32)
        return jnp.where(
            pos >= 0, jnp.take(edge_vals, jnp.maximum(pos, 0)), 0.0
        ).astype(jnp.float32)

    out = dict(arrs)
    out["tc_seg_vals"] = from_pos(arrs["tc_seg_pos"])
    out["vpu_seg_vals"] = from_pos(arrs["vpu_seg_pos"])
    return out


def spmm_dense_oracle(a_dense: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.asarray(a_dense, np.float64) @ np.asarray(b, np.float64)


def sddmm_dense_oracle(a_dense: np.ndarray, x: np.ndarray, y: np.ndarray):
    """Full dense S = X·Yᵀ sampled at a_dense's non-zeros → CSR-ordered vals."""
    s = np.asarray(x, np.float64) @ np.asarray(y, np.float64).T
    rows, cols = np.nonzero(a_dense)
    order = np.lexsort((cols, rows))
    return s[rows[order], cols[order]]
