"""Mixture-of-Experts block (qwen3-moe, moonshot/moonlight).

Dispatch is a *sparse matrix multiplication*: the token→expert assignment
matrix D (tokens × E·C, top-k ones per row) multiplies the token matrix —
exactly the extreme-sparse regime of the paper's Figure 1 (every non-zero
column vector is NNZ-1), so Libra's 2D-aware analysis assigns it to the
flexible path. The production implementation below *is* that decision:
a sort-based gather/scatter (VPU-style, zero redundancy) rather than a
one-hot dense einsum on the MXU (which would be >99% zero-padding FLOPs).
``moe_dispatch_libra_demo`` in examples/ runs the same dispatch through
the actual LibraSpMM operator to show the correspondence.

Expert compute runs as (E, C, d)×(E, d, f) batched matmuls, sharded over
the ``model`` axis (expert parallelism); XLA inserts the all-to-all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ArchConfig


def init_moe(rng, cfg: ArchConfig):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    pd = L.dtype_of(cfg, "param_dtype")
    p = {
        "router": (jax.random.normal(k1, (d, e)) / np.sqrt(d)).astype(jnp.float32),
        "wi_gate": (jax.random.normal(k2, (e, d, f)) / np.sqrt(d)).astype(pd),
        "wi_up": (jax.random.normal(k3, (e, d, f)) / np.sqrt(d)).astype(pd),
        "wo": (jax.random.normal(k4, (e, f, d)) / np.sqrt(f)).astype(pd),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(k5, cfg, d_ff=cfg.n_shared_experts * f)
    return p


def router_topk(logits, k: int):
    """Top-k routing with renormalized weights + aux load-balance loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E · Σ_e f_e · P_e
    e = logits.shape[-1]
    f_e = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(f_e * p_e)
    return topv, topi, aux


def _local_dispatch(xg, topi, topv, e: int, k: int, cap: int, cd):
    """Dispatch one token group (runs per batch shard under vmap).

    xg: (t, d); topi/topv: (t, k). Returns buf (e, cap, d) plus combine
    metadata. *Gather-formulated*: the only scatters carry int32 indices
    (t·k and e·cap elements); the token features move through row
    gathers, which GSPMD shards by output — a data-carrying scatter here
    would be lowered as replicate+select+all-reduce of the full buffer
    per layer (§Perf iteration 1b, 8.6 GB/layer of all-reduce).
    """
    t, d = xg.shape
    flat_e = topi.reshape(-1)  # (t·k,)
    order = jnp.argsort(flat_e)  # local sort, t·k elements
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, e * cap)
    src_token = order // k
    # slot → token (int32 scatter) then row-gather the features.
    tok_of_slot = jnp.zeros(e * cap + 1, jnp.int32).at[dest].set(
        src_token.astype(jnp.int32))
    valid_slot = jnp.zeros(e * cap + 1, bool).at[dest].set(keep)
    buf = jnp.where(valid_slot[:-1, None], xg[tok_of_slot[:-1]], 0).astype(cd)
    # (token, k) → slot (int32 scatter) for the combine gather.
    slot_of_assign = jnp.full(t * k, e * cap, jnp.int32).at[order].set(
        jnp.where(keep, dest, e * cap).astype(jnp.int32))
    return buf.reshape(e, cap, d), slot_of_assign.reshape(t, k)


def _local_combine(y, slot_of_assign, topv, cd):
    """y: (e, cap, d) expert outputs for one group → (t, d) tokens,
    via a row gather per (token, k) assignment (dropped → zero row)."""
    e_cap = y.shape[0] * y.shape[1]
    d = y.shape[-1]
    y_flat = jnp.concatenate([y.reshape(e_cap, d),
                              jnp.zeros((1, d), y.dtype)])
    picked = y_flat[slot_of_assign]  # (t, k, d) gather
    return (picked * topv[..., None].astype(y.dtype)).sum(axis=1)


def moe_block_global_sort(p, x, cfg: ArchConfig):
    """§Perf BASELINE dispatch: one global sort over all T·k assignments.

    Kept for the before/after iteration log — a global argsort over a
    sharded 1M-token axis lowers to a distributed sort (massive
    collective-permute traffic) and a replicated (E·cap, d) dispatch
    buffer. See EXPERIMENTS.md §Perf iteration 1.
    """
    from repro.dist.sharding import constrain

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    cap = max(8, min(int(cfg.capacity_factor * t * k / e), t))
    cd = L.dtype_of(cfg, "compute_dtype")
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    topv, topi, aux = router_topk(logits, k)
    buf, slots = _local_dispatch(xf, topi, topv, e, k, cap, cd)
    buf = constrain(buf, "model", "batch", None)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(cd)))
    up = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(cd))
    y = jnp.einsum("ecf,efd->ecd", gate * up, p["wo"].astype(cd))
    y = constrain(y, "model", "batch", None)
    out = _local_combine(y, slots, topv, cd)
    if cfg.n_shared_experts:
        out = out + L.mlp_block(p["shared"], xf, cfg)
    return out.reshape(b, s, d), aux


def _moe_ep_shardmap(p, xf, topi, topv, cfg, e, k, cap, cd, mesh, ba,
                     gd, gm, tg):
    """Explicit EP via shard_map + lax.all_to_all (the production path).

    Tokens are sharded over every mesh axis (dim 0 of the (G, tg, d)
    view); each device dispatches its tg tokens locally, then one tiled
    all-to-all over the ``model`` axis swaps (expert ↔ group) so each
    model rank computes its e/gm experts over all gm peer groups. GSPMD
    could not be coaxed into this program (it replicated the full
    activation in backward — §Perf iteration 1c), so the boundary is
    written explicitly; autodiff of all_to_all gives the mirrored
    exchange in backward, and replicated weight inputs transpose into
    the data-axis gradient psum.
    """
    from jax.sharding import PartitionSpec as P

    # Keep the (B, S, D) layout end to end: resharding across a *reshape*
    # of a sharded dim trips XLA SPMD's "involuntary full
    # rematerialization" (b/433785288) in backward, replicating the whole
    # activation. With dims preserved, batch→data and seq→model resharding
    # stays a local slice / concat in both directions.
    p_tok = P(ba, "model", None)
    p_w = P("model", None, None)

    def body(wg, wu, wo, xl, il, vl):
        bl, sl, d = xl.shape
        buf, slots = _local_dispatch(xl.reshape(bl * sl, d),
                                     il.reshape(bl * sl, k),
                                     vl.reshape(bl * sl, k), e, k, cap, cd)
        if gm > 1:  # EP all-to-all: (e, cap, d) → (e/gm, gm·cap, d)
            buf = jax.lax.all_to_all(buf, "model", 0, 1, tiled=True)
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        up = jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", gate * up, wo)
        if gm > 1:  # mirror exchange back to the owning groups
            y = jax.lax.all_to_all(y, "model", 1, 0, tiled=True)
        out = _local_combine(y, slots, vl.reshape(bl * sl, k), cd)
        return out.reshape(bl, sl, d)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_w, p_w, p_w, p_tok, p_tok, p_tok),
        out_specs=p_tok, check_vma=False,
    )(p["wi_gate"].astype(cd), p["wi_up"].astype(cd), p["wo"].astype(cd),
      xf, topi, topv.astype(cd))


def moe_block(p, x, cfg: ArchConfig):
    """x: (B, S, D) → (B, S, D), plus aux loss.

    Group-local sort-based dispatch: tokens are reshaped into G groups
    (G = number of batch shards), each group dispatches *locally* (the
    argsort/rank/scatter never cross a shard), and the dispatch buffer is
    constrained (G:batch, E:model) — GSPMD turns that boundary into the
    single device-to-expert all-to-all of production MoE, instead of a
    global 1M-token sort (the baseline's 3000s collective term; see
    EXPERIMENTS.md §Perf iteration 1).
    """
    from repro.dist.sharding import (batch_shard_count, constrain,
                                     current_mesh_info, model_axis_size)

    if cfg.moe_dispatch == "global_sort":
        return moe_block_global_sort(p, x, cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    # Two-level grouping (GShard/DeepSpeed-MoE): tokens sharded over BOTH
    # mesh axes — the batch dim over the data axes and the sequence dim
    # over the model axis (sequence-parallel MoE section). Each device
    # dispatches its own (b/gd)·(s/gm) tokens; the (gm ↔ E) boundary is
    # one tiled all-to-all carrying capacity·d per expert. Leaving tokens
    # replicated over the model axis (§Perf iterations 1a/1b) made every
    # combine intermediate gm× larger.
    gd = batch_shard_count()
    gm = model_axis_size()
    if b % gd:
        gd = 1
    if s % gm or e % max(gm, 1):
        gm = 1
    tg = (b // gd) * (s // gm)
    cap = int(cfg.capacity_factor * tg * k / e)
    cap = max(4, min(cap, tg))
    cd = L.dtype_of(cfg, "compute_dtype")

    logits = x.astype(jnp.float32) @ p["router"]  # (B, S, e)
    topv, topi, aux = router_topk(logits, k)

    mesh, ba = current_mesh_info()
    if mesh is not None and gm > 1:
        out = _moe_ep_shardmap(p, x, topi, topv, cfg, e, k, cap, cd,
                               mesh, ba, gd, gm, tg)
    else:
        # No mesh (smoke tests) or seq too short for SP (decode): local
        # dispatch; EP via the (E:model) constraint — fine at decode
        # sizes (a few hundred tokens).
        t = b * s
        buf, slots = _local_dispatch(
            x.reshape(t, d), topi.reshape(t, k),
            topv.reshape(t, k).astype(cd), e, k, cap, cd)
        buf = constrain(buf, "model", None, None)
        gate = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(cd)))
        up = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(cd))
        y = jnp.einsum("ecf,efd->ecd", gate * up, p["wo"].astype(cd))
        y = constrain(y, "model", None, None)
        out = _local_combine(y, slots, topv.reshape(t, k).astype(cd), cd)
        out = out.reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + L.mlp_block(p["shared"], x, cfg)
    return out, aux


def init_moe_layer(rng, cfg: ArchConfig):
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "mlp_norm": L.init_norm(cfg),
        "moe": init_moe(k2, cfg),
    }


def init_params(rng, cfg: ArchConfig):
    ke, kl = jax.random.split(rng)
    stacked = jax.vmap(lambda r: init_moe_layer(r, cfg))(
        jax.random.split(kl, cfg.n_layers))
    return {
        "embed": L.init_embedding(ke, cfg),
        "layers": stacked,
        "final_norm": L.init_norm(cfg),
    }


def apply_layer(lp, x, cfg: ArchConfig, layer_idx):
    s = x.shape[1]
    h = L.rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    h = L.attention_block(lp["attn"], h, cfg, layer_window=jnp.int32(s + 1))
    x = x + h
    h = L.rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
    h, aux = moe_block(lp["moe"], h, cfg)
    return x + h, aux


def forward(params, tokens, cfg: ArchConfig):
    """Returns (logits, mean aux loss)."""
    import functools

    x = L.embed(params["embed"], tokens, cfg)
    layer_fn = functools.partial(apply_layer, cfg=cfg)
    if cfg.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )

    def body(carry, inp):
        lp, idx = inp
        x, aux = layer_fn(lp, carry, layer_idx=idx)
        return x, aux

    x, auxs = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.n_layers)))
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg), auxs.mean()


# ------------------------------------------------------------- decoding ---
def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    from repro.models import transformer

    return transformer.init_cache(cfg, batch, max_len, dtype)


def decode_step(params, cache, token, cache_len, cfg: ArchConfig):
    """Scan-stacked cache (see transformer.decode_step note)."""
    x = L.embed(params["embed"], token, cfg)
    pos = (cache_len - 1) * jnp.ones((x.shape[0], 1), jnp.int32)

    def body(carry, inp):
        x = carry
        lp, kc, vc, idx = inp
        h = L.rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
        q, k2, v2 = L.qkv_project(lp["attn"], h, cfg)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k2 = L.apply_rope(k2, pos, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice(kc, k2.astype(kc.dtype),
                                          (0, cache_len - 1, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v2.astype(vc.dtype),
                                          (0, cache_len - 1, 0, 0))
        o = L.decode_attention(q, kc, vc, cache_len)
        cd = L.dtype_of(cfg, "compute_dtype")
        x = x + (o.reshape(o.shape[0], 1, -1) @ lp["attn"]["wo"].astype(cd))
        h = L.rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
        h, _ = moe_block(lp["moe"], h, cfg)
        return x + h, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x,
        (params["layers"], cache["k"], cache["v"], jnp.arange(cfg.n_layers)))
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg), {"k": k_new, "v": v_new}
