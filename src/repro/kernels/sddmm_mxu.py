"""MXU SDDMM path as a Pallas TPU kernel.

One grid step computes one sparse TC block of scores:
``S = X[window] · Y[cols]ᵀ`` (8×KF @ KF×BK on the MXU), then samples it
with the block's bitmap — the TPU-native Bit-Decoding: every sublane
tests its own bit of the 32-bit occupancy word, ``(bitmap >> sub) & 1``,
which is the paper's per-thread ``(binary >> tid) & 1`` mapped onto the
vector unit with zero divergence and no shared memory (§4.4, Fig. 8).

Both dense operands stay in HBM. The step DMAs the block's 8-row X
window and the ``BK`` Y rows its column ids (an SMEM block) name into
VMEM (:func:`repro.kernels.gather.fetch_rows`), so Y traffic scales
with the condensed non-zeros, not with ``kcols``. The feature dimension
is tiled (``kf_tile``, the fastest grid axis) with the output block as
the accumulator, so arbitrarily wide embeddings fit; the bitmap sample
is applied on the last feature tile.

**Segment-granular launch (§4.3 Ts decomposition).** The
operand layout is the hybrid balancer's segment table: one grid step
scores a whole segment of ≤ ``Ts`` blocks sharing a window — ``bk``
becomes ``ts·bk`` concatenated condensed vectors, the step is a single
``8×kf @ kf×(ts·bk)`` dot, and the shared window's X rows are fetched
once per segment instead of once per block. Zero-bitmap cap padding
samples to zero and is never gathered by the combine, so the kernel
body is layout-agnostic (this docstring's "block"
then reads "segment").

**Heads.** With ``H`` heads of width ``c`` (the layout of
:mod:`repro.kernels.gather`) the step stacks the X window once per
head, each copy masked to its head's lanes
(:func:`repro.kernels.gather.head_masks`), into ``(H·8, kf)``: one
``(H·8)×KF @ KF×BK`` dot gives each head's scores in its own 8
sublanes, ``(nb, H·8, bk)``, accumulated over feature tiles (a head
that straddles two tiles sums both parts). The bitmap samples every
head's sublanes alike. The multi-head kernel is named ``sddmm_mxu_mh``;
the single-head one is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import WINDOW
from repro.kernels.gather import (default_interpret, fetch_rows,
                                  head_masks, lane_tile, row_view)


def _kernel(cols_ref, win_ref, bitmap_ref, x_hbm, y_hbm, out_ref, xw, rows,
            sem, *, heads, head_dim):
    f = pl.program_id(1)   # feature tile index (fastest)
    bk, _, kft = rows.shape
    lanes = lane_tile(f, kft)
    win_copy = pltpu.make_async_copy(x_hbm.at[win_ref[0, 0, 0], :, lanes], xw,
                                     sem.at[1])
    win_copy.start()
    fetch_rows(y_hbm, cols_ref, lambda g, w: rows.at[w], sem.at[0], lanes)
    win_copy.wait()

    x_win = xw[...]
    if heads:
        # (H·8, kft): head h's 8 sublanes keep head h's lanes only.
        masks = head_masks((WINDOW, kft), f * kft, heads, head_dim)
        x_win = jnp.concatenate([jnp.where(m, x_win, 0.0) for m in masks],
                                axis=0)
    # 8×KFt @ KFt×BK on the MXU ((H·8)×KFt @ KFt×BK).
    s = jax.lax.dot_general(x_win, rows[...].reshape(bk, kft),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _():
        out_ref[0] = s

    @pl.when(f != 0)
    def _():
        out_ref[0] += s

    @pl.when(f == pl.num_programs(1) - 1)
    def _():
        # Bit-Decoding sample: sublane r keeps column j iff bit r of
        # bitmap[j] is set (in every head's 8 sublanes).
        sub = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 0)
        if heads:
            sub = sub & (WINDOW - 1)
        bits = (bitmap_ref[0] >> sub) & 1
        out_ref[0] = jnp.where(bits > 0, out_ref[0], 0.0)


@functools.partial(jax.jit, static_argnames=("kf_tile", "heads", "head_dim",
                                             "interpret"))
def sddmm_mxu(tc_cols, tc_bitmap, tc_window, x, y, *, kf_tile: int = 128,
              heads: int | None = None, head_dim: int | None = None,
              interpret: bool | None = None):
    """Bitmap-sampled block scores, shape ``(nb, 8, bk)``, or
    ``(nb, H, 8, bk)`` with ``heads`` heads of width ``head_dim``.

    Args:
      tc_cols: (nb, bk) i32 sparse-block column indices.
      tc_bitmap: (nb, bk) u32 8-bit occupancy words.
      tc_window: (nb,) i32 window (row-block) ids.
      x: (nwin*8, kf) dense rows; y: (kcols, kf) dense rows; ``kf``
         must be a multiple of ``kf_tile`` (ops.py pads).
    """
    nb, bk = tc_cols.shape
    kf = x.shape[1]
    assert kf % kf_tile == 0, (kf, kf_tile)
    xw = x.reshape(-1, WINDOW, kf)
    bitmap = tc_bitmap.astype(jnp.int32).reshape(nb, 1, bk)
    rows_out = WINDOW * (heads or 1)

    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, head_dim=head_dim),
        name="sddmm_mxu_mh" if heads else "sddmm_mxu",
        grid=(nb, kf // kf_tile),
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda i, f: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1), lambda i, f: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bk), lambda i, f: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows_out, bk), lambda i, f: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, rows_out, bk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((WINDOW, kf_tile), jnp.float32),
                        pltpu.VMEM((bk, 1, kf_tile), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=default_interpret(interpret),
    )(tc_cols.reshape(nb, 1, bk), tc_window.reshape(nb, 1, 1), bitmap, xw,
      row_view(y))
    return out.reshape(nb, heads, WINDOW, bk) if heads else out
