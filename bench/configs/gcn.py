"""GCN (Kipf and Welling, ICLR 2017), ``H' = Â (H W)`` with ReLU between
layers and ``Â = D̃^-1/2 (A + I) D̃^-1/2``, ``D̃`` the row sums of ``A + I``
(the harness adds the self loops, as the configuration file says): the
program's training step for this configuration, and the plain reference
beside it.

The reference is written from the layer equations in ``jax.numpy`` over
the graph's COO lists; it shares no code with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts

step_flops = counts.gcn_step


def dims(cfg: dict, traffic: dict) -> list[int]:
    """Layer widths: the traffic's features, the hidden layers, its classes."""
    return ([traffic["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [traffic["classes"]])


def init_params(key, dims: list[int]):
    """Glorot-scaled normal weights, one ``{"w"}`` per layer."""
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b), jnp.float32) / np.sqrt(a)}
            for k, a, b in zip(keys, dims[:-1], dims[1:])]


def edge_values(graph) -> np.ndarray:
    """``Â``'s values on ``graph`` (which holds the self loops):
    ``d[row] · d[col]`` per edge, ``d = deg^-1/2`` from the row sums, as
    OGB's arxiv ``gnn.py`` precomputes them."""
    rows, cols = graph.rows(), graph.indices
    deg = np.bincount(rows, minlength=graph.nodes).astype(np.float64)
    d = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1.0)), 0.0)
    return (d[rows] * d[cols]).astype(np.float32)


def make_step(ops, cfg: dict, inputs: dict):
    """The program's jitted SGD step over ``ops``, as ``params -> (params,
    loss)``."""
    from repro.dist.gnn import make_gcn_train_step

    step = make_gcn_train_step(ops, lr=cfg["lr"])
    feats, labels, ev = inputs["feats"], inputs["labels"], inputs["edges"]
    return lambda params: step(params, feats, labels, ev)


def reference_logits(params, graph: dict, inputs: dict):
    """Forward pass over COO lists (``rows`` sorted, ``cols``)."""
    rows, cols, n = graph["rows"], graph["cols"], graph["nodes"]
    h, ev = inputs["feats"], inputs["edges"]
    for i, layer in enumerate(params):
        z = h @ layer["w"]
        h = jax.ops.segment_sum(ev[:, None] * z[cols], rows, num_segments=n,
                                indices_are_sorted=True)
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h
