"""UniMP (Shi et al., "Masked Label Prediction: Unified Message Passing
Model for Semi-Supervised Classification", IJCAI 2021, arXiv:2009.03509),
as PyG's ``TransformerConv`` with ``beta=True`` runs it in
``examples/unimp_arxiv.py``. One layer, for head ``h`` of ``H`` heads of
width ``c``:

- ``q = W_q x + b_q``, ``k = W_k x + b_k``, ``v = W_v x + b_v``;
- ``α_ij = softmax_{j ∈ N(i)}(q_i · k_j / √c)`` over each node's
  in-edges and itself (the harness adds the self loops, as the
  configuration file says);
- ``m_i = Σ_j α_ij v_j``, the heads concatenated, or averaged in the
  last layer;
- ``r_i = W_r x_i + b_r``, ``β_i = σ(w_βᵀ [r_i ‖ m_i ‖ r_i − m_i])``,
  ``out_i = β_i r_i + (1 − β_i) m_i``;
- LayerNorm, then ReLU, between layers.

This module holds the program's training step for the configuration,
the plain reference beside it, the step's FLOPs and its sparse calls'
bytes. The reference is written from the layer equations in
``jax.numpy`` over the graph's COO lists; it shares no code with the
program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import counts
from repro.dist.gnn import make_unimp_train_step

#: Attention heads of every layer, as the configuration file says (the
#: harness passes ``init_params`` and the counts no configuration).
with open(Path(__file__).with_suffix(".json")) as _f:
    HEADS = json.load(_f)["heads"]


def dims(cfg: dict, traffic: dict) -> list[int]:
    """Layer widths: the traffic's features, the hidden layers, its classes."""
    return ([traffic["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [traffic["classes"]])


def _head_dims(dims: list[int], n_heads: int) -> list[int]:
    """Each layer's head width: the hidden layers split their width over
    the heads; the last layer's heads each have its full width."""
    return [d // n_heads for d in dims[1:-1]] + [dims[-1]]


def init_params(key, dims: list[int]):
    """Per layer: query, key and value weights ``(d_in, H, c)`` and
    biases ``(H, c)``, the skip path's ``(d_in, d_out)`` and ``(d_out,)``,
    the gate's ``(3 d_out,)`` and, but in the last layer, the
    LayerNorm's scale and shift. Weights Glorot-scaled normal, biases
    uniform in ``±1/√d_in``, LayerNorm 1 and 0."""
    from repro.models.gnn import init_unimp

    return init_unimp(key, dims, HEADS)


def edge_values(graph) -> None:
    """UniMP computes its edge values; it takes none."""
    return None


def make_step(ops, cfg: dict, inputs: dict):
    """The program's jitted SGD step over ``ops``, as ``params -> (params,
    loss)``."""
    step = make_unimp_train_step(ops, lr=cfg["lr"])
    feats, labels = inputs["feats"], inputs["labels"]
    return lambda params: step(params, feats, labels)


def _layer(lp, h, rows, cols, n, concat):
    """One attention layer over COO lists (``rows`` sorted)."""
    _, n_heads, c = lp["q_w"].shape
    q = jnp.einsum("nd,dhc->nhc", h, lp["q_w"]) + lp["q_b"]
    k = jnp.einsum("nd,dhc->nhc", h, lp["k_w"]) + lp["k_b"]
    v = jnp.einsum("nd,dhc->nhc", h, lp["v_w"]) + lp["v_b"]
    # A Python float keeps the scores in the operands' dtype (a NumPy
    # scalar would promote a bfloat16 control to float32 from here on).
    s = jnp.sum(q[rows] * k[cols], axis=-1) / math.sqrt(c)   # (edges, H)
    top = jax.ops.segment_max(s, rows, num_segments=n,
                              indices_are_sorted=True)
    e = jnp.exp(s - top[rows])
    z = jax.ops.segment_sum(e, rows, num_segments=n, indices_are_sorted=True)
    att = e / z[rows]
    m = jax.ops.segment_sum(att[..., None] * v[cols], rows, num_segments=n,
                            indices_are_sorted=True)        # (n, H, c)
    m = m.reshape(n, n_heads * c) if concat else m.mean(axis=1)
    r = h @ lp["r_w"] + lp["r_b"]
    beta = jax.nn.sigmoid(
        jnp.concatenate([r, m, r - m], axis=-1) @ lp["beta_w"])[:, None]
    return beta * r + (1.0 - beta) * m


def reference_logits(params, graph: dict, inputs: dict):
    """Forward pass over COO lists (``rows`` sorted, ``cols``). Each
    layer is recomputed in the backward pass rather than kept, so that
    the edge-wise intermediates of one layer at a time are held."""
    rows, cols, n = graph["rows"], graph["cols"], graph["nodes"]
    layer = jax.checkpoint(_layer, static_argnums=(4, 5))
    h = inputs["feats"]
    for i, lp in enumerate(params):
        last = i == len(params) - 1
        h = layer(lp, h, rows, cols, n, not last)
        if not last:
            mu = h.mean(axis=-1, keepdims=True)
            var = jnp.var(h, axis=-1, keepdims=True)
            h = (h - mu) / jnp.sqrt(var + 1e-5) * lp["ln_g"] + lp["ln_b"]
            h = jax.nn.relu(h)
    return h


def step_flops(nodes: int, edges: int, dims: list[int]) -> int:
    """One SGD step, per layer of width ``w = H c`` (``d_out`` the
    layer's output width, ``d_out = w`` but in the last layer, ``c``):

    - forward: the q, k, v and skip projections, the gate's
      ``(nodes × 3 d_out) @ (3 d_out)``, the SDDMM ``q k`` and the SpMM
      ``α v``;
    - backward: the same projections' and the gate's weight gradients;
      the gate's input gradient; the SpMM ``αᵀ dm`` (into v), the SDDMM
      ``dα`` and the two SpMMs of the scores' gradient into q and k
      (needed in every layer: q, k and v have weights); and, but in the
      first layer, the input gradients of the four projections.
    """
    n_heads = HEADS
    total = 0
    for i, (d_in, d_out, c) in enumerate(zip(dims[:-1], dims[1:],
                                             _head_dims(dims, n_heads))):
        w = n_heads * c
        proj = 3 * counts.dense(nodes, d_in, w) + counts.dense(nodes, d_in,
                                                               d_out)
        gate = counts.dense(nodes, 3 * d_out, 1)
        total += proj + gate                                       # forward
        total += counts.sddmm(edges, w) + counts.spmm(edges, w)
        total += proj + 2 * gate                                   # dW, dgate
        total += counts.sddmm(edges, w) + 3 * counts.spmm(edges, w)
        if i > 0:
            total += proj                                          # dH
    return total


def sparse_calls(nodes: int, edges: int, dims: list[int]) -> list[tuple]:
    """The step's multi-head sparse calls, ``(op, nnz, m, k, width,
    heads)``: per layer at ``width = H c``, the forward SDDMM and SpMM and
    the backward SpMM ``αᵀ dm``, SDDMM ``dα`` and two SpMMs into q and k
    (the order is not the program's)."""
    n_heads = HEADS
    calls = []
    for c in _head_dims(dims, n_heads):
        w = n_heads * c
        calls += [("sddmm", edges, nodes, nodes, w, n_heads)] * 2
        calls += [("spmm", edges, nodes, nodes, w, n_heads)] * 4
    return calls


def call_flops(op: str, nnz: int, m: int, k: int, width: int,
               heads: int) -> int:
    """Operations of one call: a multiply-add per non-zero and feature."""
    return 2 * nnz * width


def call_bytes(op: str, nnz: int, m: int, k: int, width: int,
               heads: int) -> int:
    """Compulsory HBM bytes of one call in float32, as
    ``bench/scopes.py`` counts them at one head: the sparse matrix's
    values (one per head for SpMM), column ids and row pointers once,
    each dense operand once, the output once (one score per non-zero and
    head for SDDMM)."""
    if op == "spmm":
        return 4 * (nnz * heads + nnz + m + 1 + k * width + m * width)
    return 4 * (2 * nnz + m + 1 + m * width + k * width + nnz * heads)
