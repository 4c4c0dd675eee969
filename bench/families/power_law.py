"""A directed graph whose out- and in-degrees are both heavy-tailed
(Pareto weights): the citation-graph regime (ogbn-arxiv). Copied from the
program's ``repro.sparse.generate.power_law_graph`` so that later changes
to the program cannot move the yardstick."""
from __future__ import annotations

import numpy as np

from bench.graphgen import Graph, from_keys


def draw(rng: np.random.Generator, nodes: int, edges: int,
         pareto_shape: float) -> Graph:
    """Exactly ``edges`` distinct directed edges, no self loops; out- and
    in-degree weights Pareto(``pareto_shape``) + 1."""
    n = nodes
    w_out = rng.pareto(pareto_shape, n) + 1.0
    w_in = rng.pareto(pareto_shape, n) + 1.0
    keys = np.zeros(0, np.int64)
    while keys.size < edges:
        draw_n = int(1.25 * (edges - keys.size)) + 16
        rows = rng.choice(n, size=draw_n, p=w_out / w_out.sum())
        cols = rng.choice(n, size=draw_n, p=w_in / w_in.sum())
        fresh = rows.astype(np.int64) * n + cols
        keys = np.unique(np.concatenate([keys, fresh[rows != cols]]))
    keys = np.sort(rng.choice(keys, size=edges, replace=False))
    return from_keys(n, keys)
