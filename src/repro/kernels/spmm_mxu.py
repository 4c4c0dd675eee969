"""MXU (Tensor-core analogue) SpMM path as a Pallas TPU kernel.

One grid step multiplies one condensed ``8×BK`` TC block by the ``BK``
rows of the dense matrix B its column ids name, for one ``nt``-lane
tile of the output: an ``8×BK @ BK×nt`` MXU dot written to the block's
own ``(8, nt)`` output slot.

TPU adaptation of the paper's TCU stream (§4.4):

* **Id-driven row fetch.** B stays in HBM; the step DMAs exactly the
  ``BK`` rows its column ids (an SMEM block) name into VMEM
  (:func:`repro.kernels.gather.fetch_rows`). B traffic is
  ``blocks · BK · nt`` per lane tile — it scales with the condensed
  non-zeros, not with ``k``, and there is no k-panel grid axis.
* **One output slot per block.** Every step writes its own ``(8, nt)``
  block, so no output block is ever revisited and both grid orders are
  always legal; the caller's fused scatter-add maps each block's 8 rows
  to its window's rows of C (and sums blocks that share a window).
* **Segment-granular launch (§4.3 Ts decomposition).** The operand
  layout ops.py passes is the hybrid balancer's segment table: a "block" is a
  *segment* of ≤ ``Ts`` condensed blocks of a single window, flattened
  to an ``(8, ts·bk)`` operand (the sum of per-block ``8×bk @ bk×nt``
  products is one ``8×(ts·bk) @ (ts·bk)×nt`` product). Per-step work is
  bounded by ``Ts`` no matter how long a power-law window is. Segments
  marked ``atomic`` (decomposed windows, or windows shared with the VPU
  path) share scatter rows with another producer; non-atomic segments
  own their rows, so the add degenerates to a store for them. A table
  of one block per segment (``ts=1``) is the per-block launch.

**Heads.** With ``H`` heads of width ``c`` (the layout of
:mod:`repro.kernels.gather`) a block carries one ``8×bk`` value matrix
per head, laid out head-major as ``(8, H·bk)``. The step stacks the
fetched rows once per head, each masked to its head's lanes
(:func:`repro.kernels.gather.head_masks`), into ``(H·bk, nt)``: one
``8×(H·bk) @ (H·bk)×nt`` dot gives every lane its own head's sum. The
multi-head kernel is named ``spmm_mxu_mh``; the single-head one is
unchanged.

Grid order (``grid_order``, chosen by
:func:`repro.kernels.ops.spmm_apply` from the call's lane-tile count —
paper §4.2's occupancy-aware scheduling choice): ``"n_outer"`` is
``(n/nt, nb)``, ``"block_outer"`` is ``(nb, n/nt)`` and fetches each
block's values once instead of once per lane tile.
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

GRID_ORDERS = ("n_outer", "block_outer")


def _kernel(cols_ref, vals_ref, b_hbm, out_ref, rows, sem, *, lane_axis,
            heads, head_dim):
    nt = out_ref.shape[2]
    lanes = lane_tile(pl.program_id(lane_axis), nt)
    fetch_rows(b_hbm, cols_ref, lambda g, w: rows.at[w], sem, lanes)
    bk = rows.shape[0]
    vals = vals_ref[0]
    b_rows = rows[...].reshape(bk, nt)
    if heads:
        # (H·bk, nt): head h's block of rows keeps head h's lanes only.
        masks = head_masks((bk, nt), pl.program_id(lane_axis) * nt, heads,
                           head_dim)
        b_rows = jnp.concatenate([jnp.where(m, b_rows, 0.0) for m in masks],
                                 axis=0)
    # 8×BK @ BK×NT on the MXU (8×(H·BK) @ (H·BK)×NT), f32 accumulation.
    out_ref[0] = jax.lax.dot_general(
        vals, b_rows, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("nt", "grid_order", "head_dim", "interpret"))
def spmm_mxu(tc_vals, tc_cols, b, *, nt: int = 128,
             grid_order: str = "n_outer", head_dim: int | None = None,
             interpret: bool | None = None):
    """Per-block TC partial output, shape ``(nb * 8, n)``.

    Args:
      tc_vals: (nb, 8, bk) f32 condensed blocks (zero padded). Under the
        segmented launch a "block" is one §4.3 segment — ``bk`` is then
        ``ts · bk`` flattened condensed vectors of a single window.
        Multi-head: (nb, 8, bk, H), one value per head (``head_dim``
        given).
      tc_cols: (nb, bk) i32 source column of each condensed vector.
      b: (k, n) dense matrix; n must be a multiple of ``nt`` (ops.py
         pads).
      grid_order: "n_outer" or "block_outer" (see module docstring).
      head_dim: width ``c`` of each of the H heads of ``b``'s columns
        (multi-head values only).
    """
    heads = None
    if tc_vals.ndim == 4:
        heads = tc_vals.shape[3]
        tc_vals = jnp.moveaxis(tc_vals, 3, 2).reshape(
            tc_vals.shape[0], WINDOW, -1)
    nb, bk = tc_cols.shape
    vk = tc_vals.shape[2]
    k, n = b.shape
    assert n % nt == 0, (n, nt)
    assert grid_order in GRID_ORDERS, grid_order

    if grid_order == "n_outer":
        grid, lane_axis = (n // nt, nb), 0
        cols_map = lambda j, i: (i, 0, 0)   # noqa: E731
        vals_map = lambda j, i: (i, 0, 0)   # noqa: E731
        out_map = lambda j, i: (i, 0, j)    # noqa: E731
    else:
        grid, lane_axis = (nb, n // nt), 1
        cols_map = lambda i, j: (i, 0, 0)   # noqa: E731
        vals_map = lambda i, j: (i, 0, 0)   # noqa: E731
        out_map = lambda i, j: (i, 0, j)    # noqa: E731

    out = pl.pallas_call(
        functools.partial(_kernel, lane_axis=lane_axis, heads=heads,
                          head_dim=head_dim),
        name="spmm_mxu_mh" if heads else "spmm_mxu",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), cols_map, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, WINDOW, vk), vals_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, WINDOW, nt), out_map),
        out_shape=jax.ShapeDtypeStruct((nb, WINDOW, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, 1, nt), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=default_interpret(interpret),
    )(tc_cols.reshape(nb, 1, bk), tc_vals, row_view(b))
    return out.reshape(nb * WINDOW, n)
