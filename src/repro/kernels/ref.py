"""Pure-jnp oracles for every Pallas kernel (and XLA fallback paths).

These define the semantics the kernels must reproduce exactly (allclose):

* TC/MXU SpMM path: per condensed block ``P = vals @ B[cols]`` accumulated
  into the block's output window.
* VPU SpMM path: per tile ``p = Σ_j vals[j] · B[cols[j]]`` accumulated into
  the tile's output row.
* TC/MXU SDDMM path: per block ``S = X[win] @ Y[cols]ᵀ`` sampled by bitmap.
* VPU SDDMM path: per element ``s = ⟨X[row], Y[col]⟩``.

The same functions serve as the fast XLA backend on CPU (interpret-mode
Pallas is a correctness tool, not a CPU performance path).

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


def spmm_tc_compact_ref(tc_vals, tc_cols, tc_rank, b, n_active):
    """Compacted-layout oracle for :func:`repro.kernels.spmm_mxu.spmm_mxu`:
    ``(n_active*8, n)`` — one 8-row slab per TC-*active* window rank.
    (The pre-compaction full-dense layout was ``rank → window`` with
    ``n_active → nwin``; the kernel no longer produces it.)"""
    gathered = jnp.take(b, tc_cols, axis=0)  # (nb, bk, n)
    if tc_vals.ndim == 4:   # (nb, 8, bk, H): each head's columns apart
        nb, bk, n = gathered.shape
        gathered = gathered.reshape(nb, bk, tc_vals.shape[3], -1)
        partial = jnp.einsum("bskh,bkhc->bshc", tc_vals,
                             gathered).reshape(nb, WINDOW, n)
    else:
        partial = jnp.einsum("bsk,bkn->bsn", tc_vals, gathered)  # (nb, 8, n)
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


def spmm_vpu_ref(vpu_vals, vpu_cols, vpu_row, b, m):
    """(nt,ts)×(nt,ts) → rows of (m, n)."""
    gathered = jnp.take(b, vpu_cols, axis=0)  # (nt, ts, n)
    partial = _vpu_partials(vpu_vals, gathered)  # (nt, n)
    return jax.ops.segment_sum(partial, vpu_row, num_segments=m,
                               indices_are_sorted=True)


def spmm_hybrid_ref(arrs, b, m, nwin):
    """Single-pass hybrid reference mirroring the fused Pallas epilogue:
    compacted TC partials, then VPU tile partials, scatter-added into C.
    Ranks, active rows and VPU rows are non-decreasing in every plan, so
    each scatter is a sorted one."""
    tc_rows = arrs["tc_active_row"]
    tc = spmm_tc_compact_ref(arrs["tc_vals"], arrs["tc_cols"],
                             arrs["tc_rank"], b, tc_rows.shape[0] // WINDOW)
    gathered = jnp.take(b, arrs["vpu_cols"], axis=0)  # (nt, ts, n)
    partials = _vpu_partials(arrs["vpu_vals"], gathered)
    out = jnp.zeros((nwin * WINDOW, b.shape[1]), tc.dtype)
    out = out.at[tc_rows].add(tc, indices_are_sorted=True)
    return out.at[arrs["vpu_row"]].add(partials, indices_are_sorted=True)[:m]


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


def sddmm_vpu_ref(rows, cols, mask, x, y, heads=None):
    """Element scores: (nt, ts) = ⟨X[row], Y[col]⟩ where mask;
    (nt, ts, H) per head with ``heads``."""
    xg = jnp.take(x, rows, axis=0)  # (nt, ts, kf)
    yg = jnp.take(y, cols, axis=0)
    if heads:
        s = jnp.einsum("tjhc,tjhc->tjh", xg.reshape(*rows.shape, heads, -1),
                       yg.reshape(*cols.shape, heads, -1))
        return jnp.where(mask[..., None], s, 0.0)
    s = jnp.einsum("tjk,tjk->tj", xg, yg)
    return jnp.where(mask, s, 0.0)


def sddmm_hybrid_ref(arrs, x, y, nnz, heads=None):
    """Hybrid SDDMM producing the canonical nnz-ordered value vector,
    ``(nnz, H)`` with ``heads`` (single fused scatter; slot nnz swallows
    -1/masked padding)."""
    s_tc = sddmm_tc_ref(arrs["tc_cols"], arrs["tc_bitmap"], arrs["tc_window"],
                        x, y, heads)
    s_el = sddmm_vpu_ref(arrs["vpu_rows"], arrs["vpu_cols"], arrs["vpu_mask"],
                         x, y, heads)
    pos_tc = jnp.where(arrs["tc_out_pos"] >= 0, arrs["tc_out_pos"], nnz)
    pos_el = jnp.where(arrs["vpu_mask"], arrs["vpu_out_pos"], nnz)
    pos = jnp.concatenate([pos_tc.reshape(-1), pos_el.reshape(-1)])
    tail = (heads,) if heads else ()
    data = jnp.concatenate([s_tc.reshape((-1,) + tail),
                            s_el.reshape((-1,) + tail)])
    out = jnp.zeros((nnz + 1,) + tail, s_tc.dtype).at[pos].add(data)
    return out[:nnz]


def revalue_spmm_arrays(arrs, edge_vals):
    """Rebuild plan value tensors from a runtime per-edge value vector.

    The sparsity pattern (and hence the whole Libra plan) is fixed; only
    values change — e.g. GNN attention weights per step. ``edge_vals``
    follows canonical CSR nnz order: ``(nnz,)``, or ``(nnz, H)`` with one
    value per head, which gives each value tensor a trailing head axis.
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
    # Lazy backend views may omit compact pos maps when only the
    # segment stream is served (see PlanArrays.for_backend).
    if "tc_pos" in arrs:
        out["tc_vals"] = from_pos(arrs["tc_pos"])
    if "vpu_pos" in arrs:
        out["vpu_vals"] = from_pos(arrs["vpu_pos"])
    # Segment-granular launch tables (§4.3) carry their own value
    # tensors; their pos maps are −1 on padding, which from_pos zeroes.
    if "tc_seg_pos" in arrs:
        out["tc_seg_vals"] = from_pos(arrs["tc_seg_pos"])
    if "vpu_seg_pos" in arrs:
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
