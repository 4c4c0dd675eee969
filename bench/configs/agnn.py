"""The program's AGNN variant (after Thekumparampil et al.,
arXiv:1803.03735): per layer, attention ``P = softmax_row(β · cos(h_i,
h_j))`` over each node's in-edges and itself (the harness adds the self
loops, as the configuration file says), aggregation ``P H``, then a
linear layer of the layer's own, with ReLU between layers. The paper's
model has one linear layer before its propagation layers and one after,
and its propagation layers carry only ``β``. This module holds the
program's training step for the configuration, and the plain reference
beside it.

The reference is written from the layer equations in ``jax.numpy`` over
the graph's COO lists; it shares no code with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts

step_flops = counts.agnn_step


def dims(cfg: dict, traffic: dict) -> list[int]:
    """Layer widths: the traffic's features, the hidden layers, its classes."""
    return ([traffic["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [traffic["classes"]])


def init_params(key, dims: list[int]):
    """Glorot-scaled normal weights and ``β = 1`` per layer."""
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b), jnp.float32) / np.sqrt(a),
             "beta": jnp.ones((), jnp.float32)}
            for k, a, b in zip(keys, dims[:-1], dims[1:])]


def edge_values(graph) -> None:
    """AGNN computes its edge values; it takes none."""
    return None


def make_step(ops, cfg: dict, inputs: dict):
    """The program's jitted SGD step over ``ops``, as ``params -> (params,
    loss)``."""
    from repro.dist.gnn import make_agnn_train_step

    step = make_agnn_train_step(ops, lr=cfg["lr"])
    feats, labels = inputs["feats"], inputs["labels"]
    return lambda params: step(params, feats, labels)


def _propagate(h, beta, rows, cols, n):
    """One attention propagation ``P H`` over COO lists."""
    hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-9)
    s = jnp.sum(hn[rows] * hn[cols], axis=-1) * beta
    top = jax.ops.segment_max(s, rows, num_segments=n,
                              indices_are_sorted=True)
    e = jnp.exp(s - top[rows])
    z = jax.ops.segment_sum(e, rows, num_segments=n, indices_are_sorted=True)
    att = e / jnp.maximum(z[rows], 1e-9)
    return jax.ops.segment_sum(att[:, None] * h[cols], rows, num_segments=n,
                               indices_are_sorted=True)


def reference_logits(params, graph: dict, inputs: dict):
    """Forward pass over COO lists (``rows`` sorted, ``cols``). Each
    propagation is recomputed in the backward pass rather than kept, so
    that the edge-wise intermediates of one layer at a time are held."""
    rows, cols, n = graph["rows"], graph["cols"], graph["nodes"]
    propagate = jax.checkpoint(_propagate, static_argnums=(4,))
    h = inputs["feats"]
    for i, layer in enumerate(params):
        h = propagate(h, layer["beta"], rows, cols, n) @ layer["w"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h
