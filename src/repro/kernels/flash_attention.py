"""Fused flash-attention forward kernel (Pallas TPU) — beyond-paper §Perf.

Every train/prefill cell's memory term is dominated by the unfused
attention chain: XLA materializes the (B, KV, G, Sq, chunk) score tensor
in HBM between QKᵀ, softmax, and PV (≈3 HBM passes over a tensor ~128×
larger than Q). This kernel keeps the score tile in VMEM: HBM traffic
drops to streaming Q, K, V once and writing O once.

Layout: grid (B·H, Sq/bq, Sk/bk), online softmax over the k-blocks
(innermost, revisit-consecutive output), m/l running stats in VMEM
scratch. GQA maps query head h to KV head h·KV//H in the k/v index_map.
Causal + sliding-window masking from absolute block offsets; optional
logit softcap (gemma2). Validated in interpret mode against the jnp
oracle; the MXU sees (bq, d)×(d, bk) and (bq, bk)×(bk, d) tiles.

Backward runs through a custom_vjp that recomputes attention with the
XLA online-softmax implementation (flash-style recompute; the fwd saves
only O and the logsumexp stats).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import default_interpret

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, causal, window, softcap, bq, bk, sk_valid):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk_valid
    if causal:
        mask &= kpos <= qpos
    mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30))[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention_fused(q, k, v, *, causal: bool = True,
                          window: int = 0, softcap: float = 0.0,
                          bq: int = 512, bk: int = 512,
                          interpret: bool | None = None):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D). Returns (B, Sq, H, D).

    window == 0 disables the sliding-window constraint.
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    bq = min(bq, sq)
    bk = min(bk, sk)
    sq_pad = (-sq) % bq
    sk_pad = (-sk) % bk
    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * kv, sk, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * kv, sk, d)
    if sq_pad:
        qt = jnp.pad(qt, ((0, 0), (0, sq_pad), (0, 0)))
    if sk_pad:
        kt = jnp.pad(kt, ((0, 0), (0, sk_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, sk_pad), (0, 0)))
    g = h // kv
    grid = (b * h, (sq + sq_pad) // bq, (sk + sk_pad) // bk)
    win = window if window else sk + sq + 1

    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(d), causal=causal, window=win,
        softcap=softcap, bq=bq, bk=bk, sk_valid=sk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=default_interpret(interpret),
    )(qt, kt, vt)
    out = out[:, :sq].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2)


def hbm_traffic_model(b, sq, sk, h, kv, d, chunk, dtype_bytes=2):
    """Analytic HBM bytes: fused kernel vs unfused XLA flash (per pass).

    Unfused: the (b·kv·g·sq·chunk) score tensor is written and read ~3×
    per chunk sweep (QKᵀ out, softmax in/out, PV in) in f32.
    Fused: q, k, v read once; o written once.
    """
    g = h // kv
    nchunks = (sk + chunk - 1) // chunk
    scores = b * kv * g * sq * chunk * 4  # f32
    unfused = 3 * scores * nchunks + (2 * b * sq * h * d
                                      + 2 * b * sk * kv * d) * dtype_bytes
    fused = (2 * b * sq * h * d + 2 * b * sk * kv * d * g) * dtype_bytes
    return {"unfused": float(unfused), "fused": float(fused),
            "reduction": float(unfused / max(fused, 1))}
