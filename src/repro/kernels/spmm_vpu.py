"""VPU (CUDA-core analogue) SpMM path as a Pallas TPU kernel.

One residual tile is ``TS`` non-zeros of a single output row; its
partial row is ``p = Σ_j vals[j] · B[cols[j], :]``, an element-wise
multiply-accumulate — no MXU, no zero-vector padding redundancy. This
is the paper's CUDA-core stream: fine-granularity skipping of zeros.

One grid step owns ``GROUP`` (= 8, one sublane each) tiles and one
``nt``-lane tile of the output: it DMAs the ``GROUP · TS`` B rows its
ids name from HBM into VMEM (:func:`repro.kernels.gather.fetch_rows`)
and accumulates ``(8, nt)`` partial rows, one per tile. B traffic is
``padded nnz · nt`` per lane tile, independent of ``k``.

**Segment-granular launch (§4.3 Cs decomposition).** The preferred
operand layout is the hybrid balancer's segment table: a "tile" is a
*segment* of ≤ ``Cs`` residual elements (whole tiles) of a single row —
the same kernel, a wider tile — so long power-law rows are split across
bounded grid steps and short rows don't pad up to the cap. Segments
write *partials*; the single fused scatter-accumulate in ops.py plays
the role of atomicAdd (segments are row-sorted by preprocessing, and on
TPU the one deterministic scatter replaces the paper's short/long-tile
store-vs-atomic split of §4.3 bitwise-reproducibly).

``grid_order`` (tuner-selected) permutes the two grid dimensions:
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

from repro.kernels.gather import (default_interpret, fetch_rows, lane_tile,
                                  row_view)

GRID_ORDERS = ("n_outer", "block_outer")
GROUP = 8   # tiles per grid step, one per sublane


def _kernel(cols_ref, vals_ref, b_hbm, out_ref, rows, sem, *, lane_axis):
    nt = out_ref.shape[1]
    lanes = lane_tile(pl.program_id(lane_axis), nt)
    fetch_rows(b_hbm, cols_ref, lambda g, w: rows.at[w, g], sem, lanes)
    vals = vals_ref[...]                                   # (8, ts)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for w in range(vals.shape[1]):
        acc = acc + vals[:, w:w + 1] * rows[w].reshape(out_ref.shape)
    out_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("nt", "grid_order", "interpret"))
def spmm_vpu(vpu_vals, vpu_cols, b, *, nt: int = 128,
             grid_order: str = "n_outer", interpret: bool | None = None):
    """Per-tile partial rows, shape ``(ntiles, n)`` (combined by the fused
    scatter-accumulate in ops.py).

    Args:
      vpu_vals: (ntiles, ts) f32 residual non-zero values (zero padded).
      vpu_cols: (ntiles, ts) i32 column of each value (0 where padded).
      b: (k, n) dense matrix; n a multiple of ``nt`` (ops.py pads).
      grid_order: "n_outer" or "block_outer" (see module docstring).
    """
    ntiles, ts = vpu_vals.shape
    k, n = b.shape
    assert n % nt == 0, (n, nt)
    assert grid_order in GRID_ORDERS, grid_order
    pad = (-ntiles) % GROUP
    if pad:
        vpu_vals = jnp.pad(vpu_vals, ((0, pad), (0, 0)))
        vpu_cols = jnp.pad(vpu_cols, ((0, pad), (0, 0)))
    ngroups = (ntiles + pad) // GROUP

    if grid_order == "n_outer":
        grid, lane_axis = (n // nt, ngroups), 0
        tile_map = lambda j, i: (i, 0)   # noqa: E731
        out_map = lambda j, i: (i, j)    # noqa: E731
    else:
        grid, lane_axis = (ngroups, n // nt), 1
        tile_map = lambda i, j: (i, 0)   # noqa: E731
        out_map = lambda i, j: (i, j)    # noqa: E731

    out = pl.pallas_call(
        functools.partial(_kernel, lane_axis=lane_axis),
        name="spmm_vpu",
        grid=grid,
        in_specs=[
            pl.BlockSpec((GROUP, ts), tile_map,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((GROUP, ts), tile_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((GROUP, nt), out_map),
        out_shape=jax.ShapeDtypeStruct((ngroups * GROUP, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ts, GROUP, 1, nt), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=default_interpret(interpret),
    )(vpu_cols, vpu_vals, row_view(b))
    return out[:ntiles]
