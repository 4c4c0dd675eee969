"""Shared id-driven row fetch for the four sparse kernels.

Every kernel multiplies against rows of one dense operand (B rows for
SpMM, X/Y rows for SDDMM) named by the plan's column or row ids. The
operand stays in HBM (``memory_space=pl.ANY``); the ids of the current
grid step sit in SMEM, and the step DMAs exactly the rows it names into
a VMEM scratch before computing. B traffic therefore scales with the
plan's padded nnz, not with ``k`` — no k-panel sweep, no in-kernel
gather on a resident panel. Each (id, lane tile) pair is copied once
per grid step, so the exactly-once accounting is the plan's: padding
ids carry zero values (SpMM) or are routed to the combine's swallow
slot (SDDMM). Where the plan knows that a row of ids ends in padding
(the VPU SpMM's segment lengths), the fetch stops at its real prefix.

The dense operand is viewed as ``(rows, 1, width)`` (:func:`row_view`)
so one row is a whole trailing ``(1, width)`` tile slice — a single-row
slice of an ``(8, 128)``-tiled 2-D HBM array is refused by Mosaic.

**Multi-head layout.** With ``H`` heads of width ``c`` the dense
operands hold the heads contiguously in the feature axis: head ``h``
owns features ``[h·c, (h+1)·c)``, with no padding between heads, and
the edge values carry one value per head. A lane tile may hold several
heads, or part of one (at ``c = 40`` head 3 straddles the first two
128-lane tiles): each kernel tells a lane's head by a per-lane map
(:func:`head_masks`) from its global feature index, so SpMM scales each
lane by its own head's value and SDDMM sums each head's lanes apart,
accumulating a straddling head's partial sums across feature tiles.
Lanes past ``H·c`` (the padding to whole tiles) belong to no head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def default_interpret(interpret: bool | None) -> bool:
    """Compiled kernels on a TPU, the Pallas interpreter elsewhere;
    an explicit ``interpret`` wins (a described-chip compile passes
    ``False`` from a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def row_view(x: jnp.ndarray) -> jnp.ndarray:
    """``(rows, width)`` → ``(rows, 1, width)`` for row-granular DMA."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def fetch_rows(src, ids, dst_of, sem, lanes, lens=None) -> None:
    """DMA ``src[ids[..., g, w], :, lanes]`` into ``dst_of(g, w)`` for
    every entry of the SMEM id block ``ids`` (shape ``(G, W)``, or
    ``(1, G, W)`` for a one-segment block), then wait for all of them.
    Ids are clamped into ``src`` so a corrupt plan can never address
    past the operand.

    ``lens`` (optional: ``G`` scalar lengths, read from SMEM) bounds the
    fetch to the first ``lens[g]`` entries of row ``g`` of ids (clamped
    to ``[0, W]``): the real elements of a segment whose padding is a
    suffix. The caller must not read ``dst_of(g, w)`` for
    ``w ≥ lens[g]``."""
    *lead, g_n, w_n = ids.shape
    lead = (0,) * len(lead)
    hi = src.shape[0] - 1

    def start(g, w):
        row = jnp.minimum(jnp.maximum(ids[lead + (g, w)], 0), hi)
        pltpu.make_async_copy(src.at[row, :, lanes], dst_of(g, w),
                              sem).start()

    def start_flat(t, carry):
        start(t // w_n, t % w_n)
        return carry

    def wait(t, carry):
        # Every copy has the same size, so any same-shaped descriptor
        # waits off one completion.
        pltpu.make_async_copy(src.at[0, :, lanes], dst_of(0, 0),
                              sem).wait()
        return carry

    if lens is None:
        count = g_n * w_n
        jax.lax.fori_loop(0, count, start_flat, 0)
    else:
        count = jnp.int32(0)
        for g, n_g in enumerate(lens):
            n_g = jnp.minimum(jnp.maximum(n_g, 0), w_n)
            jax.lax.fori_loop(
                0, n_g, lambda w, carry, g=g: (start(g, w), carry)[1], 0)
            count = count + n_g
    jax.lax.fori_loop(0, count, wait, 0)


def head_masks(shape, first: int, heads: int, head_dim: int) -> list:
    """One boolean mask of ``shape`` per head: lane ``l`` (the last
    axis) holds feature ``first + l``, which head ``h`` owns when it is
    in ``[h·head_dim, (h+1)·head_dim)``."""
    f = first + jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return [(f >= h * head_dim) & (f < (h + 1) * head_dim)
            for h in range(heads)]


def lane_tile(j, width: int):
    """Lane slice of the ``j``-th ``width``-wide tile (aligned hint)."""
    return pl.ds(pl.multiple_of(j * width, width), width)
