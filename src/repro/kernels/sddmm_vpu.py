"""VPU SDDMM path as a Pallas TPU kernel.

One tile is ``TS`` isolated non-zero elements:
``s[j] = ⟨X[rows[j]], Y[cols[j]]⟩``. One grid step owns ``GROUP``
(= 8, one sublane each) tiles and one feature tile: it DMAs the
``GROUP · TS`` X rows and Y rows its ids (SMEM blocks) name from HBM
into VMEM (:func:`repro.kernels.gather.fetch_rows`) and reduces their
products on the VPU — the paper's CUDA-core stream with Float4 chunks →
128-lane VMEM rows here. Operand traffic scales with the element count,
not with ``m`` or ``kcols``; the feature dimension is tiled
(``kf_tile``, the fastest grid axis) with the output block as the
accumulator.

**Segment-granular launch (§4.3 Cs cap).** SDDMM element tiles are
flat (every score owns its canonical output slot — no atomicity), so
the hybrid balancer's Cs cap simply batches ``cs/ts`` whole tiles per
grid step (``ts`` becomes the segment width); mask-False padding is
dropped by the caller's combine.

**Heads.** With ``H`` heads of width ``c`` (the layout of
:mod:`repro.kernels.gather`) each element's product row is reduced once
per head over that head's lanes
(:func:`repro.kernels.gather.head_masks`), into ``(H, ntiles, ts)``
scores accumulated over feature tiles (a head that straddles two tiles
sums both parts). The multi-head kernel is named ``sddmm_vpu_mh``; the
single-head one is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (default_interpret, fetch_rows,
                                  head_masks, lane_tile, row_view)

GROUP = 8   # tiles per grid step, one per sublane


def _kernel(rows_ref, cols_ref, x_hbm, y_hbm, out_ref, xg, yg, sem, *,
            heads, head_dim):
    f = pl.program_id(1)   # feature tile (fastest)
    kft = xg.shape[3]
    lanes = lane_tile(f, kft)
    fetch_rows(x_hbm, rows_ref, lambda g, w: xg.at[w, g], sem, lanes)
    fetch_rows(y_hbm, cols_ref, lambda g, w: yg.at[w, g], sem, lanes)
    if heads:
        _multi_head(out_ref, xg, yg, f, kft, heads, head_dim)
        return
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    s = jnp.zeros(out_ref.shape, jnp.float32)              # (8, ts)
    for w in range(out_ref.shape[1]):
        prod = (xg[w] * yg[w]).reshape(GROUP, kft)
        dot = jnp.sum(prod, axis=1, keepdims=True)         # (8, 1)
        s = jnp.where(lane == w, dot, s)

    @pl.when(f == 0)
    def _():
        out_ref[...] = s

    @pl.when(f != 0)
    def _():
        out_ref[...] += s


def _multi_head(out_ref, xg, yg, f, kft, heads, head_dim):
    """Per-head element scores of one feature tile, ``out_ref`` (H, 8,
    ts)."""
    shape = out_ref.shape[1:]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    masks = head_masks((GROUP, kft), f * kft, heads, head_dim)
    s = [jnp.zeros(shape, jnp.float32) for _ in range(heads)]
    for w in range(shape[1]):
        prod = (xg[w] * yg[w]).reshape(GROUP, kft)
        for h, m in enumerate(masks):
            dot = jnp.sum(jnp.where(m, prod, 0.0), axis=1, keepdims=True)
            s[h] = jnp.where(lane == w, dot, s[h])

    @pl.when(f == 0)
    def _():
        for h in range(heads):
            out_ref[h] = s[h]

    @pl.when(f != 0)
    def _():
        for h in range(heads):
            out_ref[h] += s[h]


@functools.partial(jax.jit, static_argnames=("kf_tile", "heads", "head_dim",
                                             "interpret"))
def sddmm_vpu(rows, cols, x, y, *, kf_tile: int = 128,
              heads: int | None = None, head_dim: int | None = None,
              interpret: bool | None = None):
    """Element scores, shape ``(ntiles, ts)``, or ``(H, ntiles, ts)``
    with ``heads`` heads of width ``head_dim`` (mask applied by the
    caller).

    ``x.shape[1]`` (= ``y.shape[1]``) must be a multiple of ``kf_tile``
    (ops.py pads).
    """
    ntiles, ts = rows.shape
    kf = x.shape[1]
    assert kf % kf_tile == 0, (kf, kf_tile)
    pad = (-ntiles) % GROUP
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
    ngroups = (ntiles + pad) // GROUP
    ids = pl.BlockSpec((GROUP, ts), lambda i, f: (i, 0),
                       memory_space=pltpu.SMEM)

    if heads:
        out_specs = pl.BlockSpec((heads, GROUP, ts), lambda i, f: (0, i, 0))
        out_shape = (heads, ngroups * GROUP, ts)
    else:
        out_specs = pl.BlockSpec((GROUP, ts), lambda i, f: (i, 0))
        out_shape = (ngroups * GROUP, ts)
    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, head_dim=head_dim),
        name="sddmm_vpu_mh" if heads else "sddmm_vpu",
        grid=(ngroups, kf // kf_tile),
        in_specs=[ids, ids, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((ts, GROUP, 1, kf_tile), jnp.float32),
                        pltpu.VMEM((ts, GROUP, 1, kf_tile), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=default_interpret(interpret),
    )(rows, cols, row_view(x), row_view(y))
    return out[:, :ntiles] if heads else out[:ntiles]
