"""VPU (CUDA-core analogue) SpMM path as a Pallas TPU kernel.

One residual tile is ``TS`` non-zeros of a single output row; its
partial row is ``p = Σ_j vals[j] · B[cols[j], :]``, an element-wise
multiply-accumulate — no MXU, no zero-vector padding redundancy. This
is the paper's CUDA-core stream: fine-granularity skipping of zeros.

One grid step owns ``GROUP`` (= 8, one sublane each) tiles and one
``nt``-lane tile of the output: it DMAs the B rows its ids name from
HBM into VMEM (:func:`repro.kernels.gather.fetch_rows`) and accumulates
``(8, nt)`` partial rows, one per tile. With per-tile lengths (the
segment tables' ``vpu_seg_len``) it fetches rows for the real elements
only, so B traffic is ``nnz · nt`` per lane tile; without them it is
``padded nnz · nt``. Either way it is independent of ``k``.

**Segment-granular launch (§4.3 Cs decomposition).** The preferred
operand layout is the hybrid balancer's segment table: a "tile" is a
*segment* of ≤ ``Cs`` residual elements (whole tiles) of a single row —
the same kernel, a wider tile — so long power-law rows are split across
bounded grid steps and short rows don't pad up to the cap. Segments
write *partials*; the single fused scatter-accumulate in ops.py plays
the role of atomicAdd (segments are row-sorted by preprocessing, and on
TPU the one deterministic scatter replaces the paper's short/long-tile
store-vs-atomic split of §4.3 bitwise-reproducibly).

**Heads.** With ``H`` heads of width ``c`` (the layout of
:mod:`repro.kernels.gather`) a slot carries ``H`` values, laid out
``(8, ts·H)`` with value ``w·H + h`` of a tile in lane ``w·H + h``: each
lane of a fetched B row is scaled by its own head's value, picked per
lane (:func:`repro.kernels.gather.head_masks`), so one call fetches
each B row once per lane tile for all heads. The multi-head kernel is
named ``spmm_vpu_mh``; the single-head one is unchanged.

``grid_order`` (chosen by :func:`repro.kernels.ops.spmm_apply` from the
call's lane-tile count) permutes the two grid dimensions:
``"n_outer"`` walks all tile groups per lane tile, ``"block_outer"``
all lane tiles per group. Both are legal: every step owns its output
block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (default_interpret, fetch_rows,
                                  head_masks, lane_tile, row_view)

GRID_ORDERS = ("n_outer", "block_outer")
GROUP = 8   # tiles per grid step, one per sublane
# Tile lengths per SMEM block: the tile of XLA's layout of a 1-D int32
# array (a (tiles, 1) column would pad each length to 128 lanes in HBM).
LENS_BLOCK = 1024


def _kernel(*refs, lane_axis, bounded, heads, head_dim):
    if bounded:
        cols_ref, lens_ref, vals_ref, b_hbm, out_ref, rows, sem = refs
        base = (pl.program_id(1 - lane_axis) % (LENS_BLOCK // GROUP)) * GROUP
        seg_lens = [lens_ref[base + g] for g in range(GROUP)]
    else:
        cols_ref, vals_ref, b_hbm, out_ref, rows, sem = refs
        seg_lens = None
    nt = out_ref.shape[1]
    lanes = lane_tile(pl.program_id(lane_axis), nt)
    fetch_rows(b_hbm, cols_ref, lambda g, w: rows.at[w, g], sem, lanes,
               lens=seg_lens)
    vals = vals_ref[...]                                   # (8, ts·H)
    if heads:
        masks = head_masks(out_ref.shape, pl.program_id(lane_axis) * nt,
                           heads, head_dim)
    if bounded:
        # Each sublane's length, broadcast over its lanes.
        sub = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        lens = jnp.zeros(out_ref.shape, jnp.int32)
        for g, n_g in enumerate(seg_lens):
            lens = jnp.where(sub == g, n_g, lens)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for w in range(rows.shape[0]):
        if heads:
            # Each lane takes its own head's value (past H·c: the last
            # head's, against B's zero padding).
            base = w * heads
            val = vals[:, base + heads - 1:base + heads]
            for h in range(heads - 1):
                val = jnp.where(masks[h], vals[:, base + h:base + h + 1],
                                val)
        else:
            val = vals[:, w:w + 1]
        row = rows[w].reshape(out_ref.shape)
        if bounded:
            # Slots past a segment's length were not fetched this step:
            # their scratch rows are stale (garbage on the first step).
            # A select, not a multiply, zeroes them, so the padded slot
            # adds 0 · 0 where the every-slot fetch adds 0 · B[0].
            row = jnp.where(w < lens, row, 0.0)
        acc = acc + val * row
    out_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("nt", "grid_order", "head_dim", "interpret"))
def spmm_vpu(vpu_vals, vpu_cols, b, vpu_lens=None, *, nt: int = 128,
             grid_order: str = "n_outer", head_dim: int | None = None,
             interpret: bool | None = None):
    """Per-tile partial rows, shape ``(ntiles, n)`` (combined by the fused
    scatter-accumulate in ops.py).

    Args:
      vpu_vals: (ntiles, ts) f32 residual non-zero values (zero padded),
        or (ntiles, ts, H) with one value per head (``head_dim`` given).
      vpu_cols: (ntiles, ts) i32 column of each value (0 where padded).
      b: (k, n) dense matrix; n a multiple of ``nt`` (ops.py pads).
      vpu_lens: optional (ntiles,) i32 real elements of each tile, whose
        real slots are a prefix (the segment tables' ``vpu_seg_len``):
        only those slots fetch a B row. Without it every slot does.
      grid_order: "n_outer" or "block_outer" (see module docstring).
      head_dim: width ``c`` of each of the H heads of ``b``'s columns
        (multi-head values only).
    """
    heads = None
    if vpu_vals.ndim == 3:
        heads = vpu_vals.shape[2]
        vpu_vals = vpu_vals.reshape(vpu_vals.shape[0], -1)
    ntiles, ts = vpu_cols.shape
    k, n = b.shape
    assert n % nt == 0, (n, nt)
    assert grid_order in GRID_ORDERS, grid_order
    bounded = vpu_lens is not None
    pad = (-ntiles) % GROUP
    if pad:
        vpu_vals = jnp.pad(vpu_vals, ((0, pad), (0, 0)))
        vpu_cols = jnp.pad(vpu_cols, ((0, pad), (0, 0)))
    ngroups = (ntiles + pad) // GROUP

    per_block = LENS_BLOCK // GROUP
    if grid_order == "n_outer":
        grid, lane_axis = (n // nt, ngroups), 0
        tile_map = lambda j, i: (i, 0)             # noqa: E731
        lens_map = lambda j, i: (i // per_block,)  # noqa: E731
        out_map = lambda j, i: (i, j)              # noqa: E731
    else:
        grid, lane_axis = (ngroups, n // nt), 1
        tile_map = lambda i, j: (i, 0)             # noqa: E731
        lens_map = lambda i, j: (i // per_block,)  # noqa: E731
        out_map = lambda i, j: (i, j)              # noqa: E731

    in_specs = [pl.BlockSpec((GROUP, ts), tile_map,
                             memory_space=pltpu.SMEM)]
    operands = [vpu_cols]
    if bounded:
        in_specs.append(pl.BlockSpec((LENS_BLOCK,), lens_map,
                                     memory_space=pltpu.SMEM))
        operands.append(jnp.pad(vpu_lens, (0, (-ntiles) % LENS_BLOCK)))
    in_specs += [pl.BlockSpec((GROUP, vpu_vals.shape[1]), tile_map),
                 pl.BlockSpec(memory_space=pl.ANY)]
    operands += [vpu_vals, row_view(b)]
    out = pl.pallas_call(
        functools.partial(_kernel, lane_axis=lane_axis, bounded=bounded,
                          heads=heads, head_dim=head_dim),
        name="spmm_vpu_mh" if heads else "spmm_vpu",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((GROUP, nt), out_map),
        out_shape=jax.ShapeDtypeStruct((ngroups * GROUP, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ts, GROUP, 1, nt), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=default_interpret(interpret),
    )(*operands)
    return out[:ntiles]
