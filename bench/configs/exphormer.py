"""Exphormer (Shirzad, Velingker, Venkatachalam, Sutherland and Sinop,
"Exphormer: Sparse Transformers for Graphs", ICML 2023, arXiv:2303.06147)
in GraphGPS blocks (arXiv:2205.12454), trained full-graph. Nodes
``0 … n−1`` are the graph's, node ``n`` is the virtual node.

- Encoder: ``h_i = x_i W_in + b_in`` for the real nodes; the virtual
  node starts at a learned ``u``.
- Edge types: ``graph``, ``A + I`` over the real nodes (the harness adds
  the self loops, as the configuration file says), and the virtual
  node's self loop; ``expander``, for each of ``expander_perms`` random
  permutations ``π`` the pairs ``(i, π(i))`` and ``(π(i), i)``, self
  pairs and repeats dropped, drawn once from ``expander_seed``;
  ``virtual``, every real node to ``n`` and ``n`` to every real node.
- Local: ``l = BN₁(h + Â h W_l + b_l)``, ``Â = D̃^-1/2 (A + I) D̃^-1/2``
  over the real nodes and ``Â_nn = 1``.
- Attention, per head of ``H`` heads of ``c``: ``q = h W_q + b_q``,
  ``k``, ``v`` alike; ``s_ij = clamp((q_i ⊙ e_t)·k_j / √c, −5, 5)`` on an
  edge of type ``t``; ``α = softmax`` over all typed edges of row ``i``
  together (a pair under two types counts twice); ``m_i = Σ_j α_ij v_j``,
  heads concatenated; ``a = BN₂(h + m W_o + b_o)``.
- Block: ``y = l + a``, ``h' = BN₃(y + W₂ ReLU(W₁ y + b₁) + b₂)``, ``W₁``
  of ``w × 2w``.
- Head: ``logits = h_real W_out + b_out``; the cross-entropy is over the
  real nodes.
- BatchNorm: batch statistics over all ``n + 1`` rows, learned scale
  and shift.

This module holds the program's training step for the configuration,
the plain reference beside it, the step's FLOPs and its sparse calls'
bytes. The reference is written from the equations above in
``jax.numpy`` over COO lists of each edge type on ``n + 1`` nodes; it
takes the program's :func:`repro.models.gnn.expander_csr` as data only
and shares no computation with the program. Its edge-wise terms are
computed by type and in blocks of edges, each recomputed in the backward
pass, so that it fits on the chip at arxiv's size.
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.configs import unimp
from bench.configs.gcn import edge_values  # noqa: F401  (Â, the GCN's)
from repro.dist.gnn import make_exphormer_train_step

with open(Path(__file__).with_suffix(".json")) as _f:
    _CFG = json.load(_f)
#: Attention heads, expander permutations and its seed, as the
#: configuration file says (the harness passes ``init_params`` and the
#: counts no configuration).
HEADS = _CFG["heads"]
PERMS = _CFG["expander_perms"]
EXPANDER_SEED = _CFG["expander_seed"]
CLAMP = float(_CFG["score_clamp"])

#: Edges per block of the reference's edge-wise terms.
EDGE_BLOCK = 1 << 20

#: The edge types in the order of the key scalings' names.
TYPES = ("graph", "expander", "virtual")


def dims(cfg: dict, traffic: dict) -> list[int]:
    """The traffic's features, the hidden width between consecutive GPS
    blocks, its classes: ``layers + 1`` entries for ``layers`` blocks."""
    return ([traffic["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [traffic["classes"]])


def init_params(key, dims: list[int]):
    """The program's :func:`repro.models.gnn.init_exphormer`: encoder,
    virtual node, per block the GCN local, query, key and value ``(w, H,
    c)``, a key scaling ``(H, c)`` per edge type, the attention's output,
    the FFN and three BatchNorms, then the head."""
    from repro.models.gnn import init_exphormer

    return init_exphormer(key, dims, HEADS)


@functools.lru_cache(maxsize=2)
def expander(nodes: int) -> tuple[np.ndarray, np.ndarray]:
    """The expander's COO lists (rows sorted) over ``nodes`` nodes."""
    from repro.models.gnn import expander_csr

    x = expander_csr(nodes, PERMS, EXPANDER_SEED)
    rows = np.repeat(np.arange(nodes, dtype=np.int32), np.diff(x.indptr))
    return rows, np.asarray(x.indices, np.int32)


def make_step(ops, cfg: dict, inputs: dict):
    """The program's jitted SGD step over ``ops`` (the graph's plans) and
    the expander's :class:`repro.models.gnn.GraphOps`, built here under
    the same ``ExecSpec``, as ``params -> (params, loss)``."""
    from repro.models.gnn import GraphOps, expander_csr

    gx = GraphOps(expander_csr(ops.m, cfg["expander_perms"],
                               cfg["expander_seed"]), spec=ops.spec)
    step = make_exphormer_train_step(ops, gx, lr=cfg["lr"])
    feats, labels, ev = inputs["feats"], inputs["labels"], inputs["edges"]
    return lambda params: step(params, feats, labels, ev)


def _blocked(rows, cols, *more):
    """``rows``, ``cols`` and the ``(H, edges)`` arrays ``more`` cut into
    whole blocks of edges, ``(blocks, block)`` and ``(blocks, H, block)``;
    the padding edges join row 0 to column 0 and carry 0."""
    size = rows.shape[0]
    block = min(EDGE_BLOCK, size)
    pad = -size % block

    def cut(x):
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        x = x.reshape(x.shape[:-1] + (-1, block))
        return jnp.moveaxis(x, -2, 0)

    return [cut(x) for x in (rows, cols, *more)]


def _heads(n_heads: int, c: int, dtype):
    """``(H c, H)``: 1 where feature ``f`` belongs to head ``h``. Per-head
    sums are products by it, so that every edge-wise array keeps its
    ``H c`` features or its edges on the last axis (a TPU pads a last
    axis of ``H`` to 128 lanes)."""
    return jnp.asarray(np.repeat(np.eye(n_heads), c, axis=0), dtype)


@jax.checkpoint
def _score_block(qe, k, rows, cols, heads):
    # ``qe`` is q ⊙ e_t; a Python float keeps the scores in the operands'
    # dtype (the bfloat16 control stays bfloat16).
    s = jnp.einsum("ef,fh->he", qe[rows] * k[cols], heads)
    c = heads.shape[0] // heads.shape[1]
    return jnp.clip(s / math.sqrt(c), -CLAMP, CLAMP)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _message_block(att, v, rows, cols, heads, nodes):
    w = jnp.einsum("he,fh->ef", att, heads)
    return jax.ops.segment_sum(w * v[cols], rows, num_segments=nodes)


def _scores(qe, k, rows, cols, heads):
    """``(H, edges)`` clamped scores of one type's edges, a block at a
    time."""
    def block(_, rc):
        return None, _score_block(qe, k, rc[0], rc[1], heads)

    _, s = jax.lax.scan(block, None, _blocked(rows, cols))
    return jnp.moveaxis(s, 0, 1).reshape(s.shape[1], -1)[:, :rows.shape[0]]


def _messages(att, v, rows, cols, heads, nodes):
    """``Σ_j att_ij v_j`` per row over one type's edges (``att`` of
    ``(H, edges)``), ``(nodes, H c)``, summed a block at a time."""
    def block(out, rca):
        r, c, a = rca
        return out + _message_block(a, v, r, c, heads, nodes), None

    out, _ = jax.lax.scan(block, jnp.zeros((nodes, v.shape[1]), v.dtype),
                          _blocked(rows, cols, att))
    return out


def _batch_norm(h, scale, shift):
    mu = jnp.mean(h, axis=0)
    var = jnp.var(h, axis=0)
    return (h - mu) / jnp.sqrt(var + 1e-5) * scale + shift


def _layer(lp, h, typed, local_vals):
    """One GPS block over ``typed``, the ``(rows, cols)`` lists of each
    type on all ``h.shape[0]`` nodes; ``local_vals`` are ``Â``'s values on
    the graph type's edges."""
    nodes, w = h.shape
    _, n_heads, c = lp["q_w"].shape
    ind = _heads(n_heads, c, h.dtype)
    g_rows, g_cols = typed[0]
    z = h @ lp["l_w"]
    agg = _messages(local_vals[None, :], z, g_rows, g_cols,
                    jnp.ones((w, 1), h.dtype), nodes)
    local = _batch_norm(h + agg + lp["l_b"], lp["bn1_g"], lp["bn1_b"])

    q = h @ lp["q_w"].reshape(w, -1) + lp["q_b"].reshape(-1)
    k = h @ lp["k_w"].reshape(w, -1) + lp["k_b"].reshape(-1)
    v = h @ lp["v_w"].reshape(w, -1) + lp["v_b"].reshape(-1)
    s = jnp.concatenate([_scores(q * lp[f"e_{t}"].reshape(-1), k, r, cl, ind)
                         for t, (r, cl) in zip(TYPES, typed)], axis=1)
    rows = jnp.concatenate([r for r, _ in typed])

    def softmax(s):
        top = jax.ops.segment_max(s, rows, num_segments=nodes)
        ex = jnp.exp(s - top[rows])
        return ex / jax.ops.segment_sum(ex, rows, num_segments=nodes)[rows]

    att = jax.vmap(softmax)(s)                          # (H, all edges)
    m, at = 0.0, 0
    for r, cl in typed:
        m = m + _messages(att[:, at:at + r.shape[0]], v, r, cl, ind, nodes)
        at += r.shape[0]
    attn = _batch_norm(h + m @ lp["o_w"] + lp["o_b"], lp["bn2_g"],
                       lp["bn2_b"])

    y = local + attn
    f = jax.nn.relu(y @ lp["f1_w"] + lp["f1_b"]) @ lp["f2_w"] + lp["f2_b"]
    return _batch_norm(y + f, lp["bn3_g"], lp["bn3_b"])


def reference_logits(params, graph: dict, inputs: dict):
    """Forward pass over COO lists (``rows`` sorted, ``cols``, the graph
    with its self loops) and the expander's, on the ``n`` real nodes and
    the virtual node ``n``. Each block is recomputed in the backward pass
    rather than kept."""
    n = graph["nodes"]
    x_rows, x_cols = (jnp.asarray(a) for a in expander(n))
    real = jnp.arange(n, dtype=jnp.int32)
    virt = jnp.full((n,), n, jnp.int32)
    loop = jnp.array([n], jnp.int32)
    typed = [(jnp.concatenate([graph["rows"].astype(jnp.int32), loop]),
              jnp.concatenate([graph["cols"].astype(jnp.int32), loop])),
             (x_rows, x_cols),
             (jnp.concatenate([real, virt]), jnp.concatenate([virt, real]))]
    ev = inputs["edges"]
    local_vals = jnp.concatenate([ev, jnp.ones((1,), ev.dtype)])
    h = jnp.concatenate([inputs["feats"] @ params["in_w"] + params["in_b"],
                         params["virtual"][None]])
    layer = jax.checkpoint(_layer)
    for lp in params["layers"]:
        h = layer(lp, h, typed, local_vals)
    return h[:n] @ params["out_w"] + params["out_b"]


def expander_nnz(nodes: int) -> int:
    return int(expander(nodes)[0].shape[0])


def step_flops(nodes: int, edges: int, dims: list[int]) -> int:
    """One SGD step. Per GPS block of width ``w`` on ``nodes + 1`` rows:

    - forward: the local, query, key, value and output products
      (``w × w``) and the FFN's two (``w × 2w``, ``2w × w``); the local
      SpMM by ``Â``; per edge type (``edges`` graph edges, the
      expander's, the virtual node's ``2 nodes + 1``) the scores (an
      SDDMM) and the messages (an SpMM);
    - backward: every product's weight gradient and input gradient (the
      encoder's weights lie below every block); the local SpMM by
      ``Âᵀ``; per edge type the SDDMM ``dα`` and three SpMMs (into v, q
      and k).

    Plus the encoder (forward and weight gradient) and the head (forward,
    weight and input gradients)."""
    d_in, w, n_out = dims[0], dims[1], dims[-1]
    rows = nodes + 1
    typed = (edges, expander_nnz(nodes), 2 * nodes + 1)
    dense = (5 * counts.dense(rows, w, w) + counts.dense(rows, w, 2 * w)
             + counts.dense(rows, 2 * w, w))
    block = 3 * dense + 2 * counts.spmm(edges, w)
    for e in typed:
        block += 2 * counts.sddmm(e, w) + 4 * counts.spmm(e, w)
    return ((len(dims) - 1) * block + 2 * counts.dense(nodes, d_in, w)
            + 3 * counts.dense(nodes, w, n_out))


def sparse_calls(nodes: int, edges: int, dims: list[int]) -> list[tuple]:
    """The step's sparse calls through Libra plans, ``(op, nnz, m, k,
    width, heads)``: per block the GCN local's SpMM and its ``Âᵀ`` SpMM
    at one head, and per sparse edge type (graph, expander) the forward
    SDDMM and SpMM, the backward SpMM ``αᵀ dm``, SDDMM ``dα`` and two
    SpMMs into q and k, all heads fused (the order is not the
    program's)."""
    w = dims[1]
    calls = []
    for _ in dims[1:]:
        calls += [("spmm", edges, nodes, nodes, w, 1)] * 2
        for e in (edges, expander_nnz(nodes)):
            calls += [("sddmm", e, nodes, nodes, w, HEADS)] * 2
            calls += [("spmm", e, nodes, nodes, w, HEADS)] * 4
    return calls


call_flops = unimp.call_flops
call_bytes = unimp.call_bytes
