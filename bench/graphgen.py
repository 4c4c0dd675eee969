"""The benchmark's one graph generator: a traffic file's parameters in,
a CSR sparsity pattern out.

A traffic mix of this benchmark is a graph, described by a data file
``bench/traffic/<mix>.json``. Its ``family`` names a module
``bench/families/<family>.py`` whose ``draw(rng, **graph)`` draws the
shape from the file's ``graph`` parameters; ``graph_seed`` is the seed it
is drawn from: a mix is one fixed graph, as a public dataset is, and
every run of a cell trains on the same one (the run's own seed draws
features, labels and weights). ``"symmetric": true`` then adds every
edge's reverse, as a training script that makes the dataset undirected
does. A configuration that adds self loops (``"self_loops": true`` in its
file) gets them from :func:`with_self_loops`.

A family meets the exact node and edge counts of its parameters, with no
self loops and no duplicate edges, and draws vectorised, so
graph-benchmark sizes build in a second or two. A new shape is a new
family module; no file here changes.

This module imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """A square sparsity pattern in CSR form. Row ``r`` lists the sources
    ``indices[indptr[r]:indptr[r + 1]]`` (sorted) whose features row ``r``
    aggregates."""

    nodes: int
    indptr: np.ndarray   # (nodes + 1,) int64
    indices: np.ndarray  # (edges,) int32

    @property
    def edges(self) -> int:
        return int(self.indices.shape[0])

    def rows(self) -> np.ndarray:
        """Destination row of each edge, in CSR order."""
        return np.repeat(np.arange(self.nodes, dtype=np.int32),
                         np.diff(self.indptr))

    def keys(self) -> np.ndarray:
        """Each edge as ``row * nodes + col``, in CSR order (sorted)."""
        return (self.rows().astype(np.int64) * self.nodes
                + self.indices.astype(np.int64))


def rng_for(seed: int) -> np.random.Generator:
    """NumPy generator for any whole-number seed, negative ones included."""
    return np.random.default_rng(seed % 2**64)


def from_keys(n: int, keys: np.ndarray) -> Graph:
    """CSR of the sorted, distinct edge keys ``row * n + col``."""
    rows = keys // n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return Graph(n, indptr, (keys % n).astype(np.int32))


def symmetrise(g: Graph) -> Graph:
    """``A ∪ Aᵀ``: every edge and its reverse, once each."""
    keys = g.keys()
    rev = g.indices.astype(np.int64) * g.nodes + g.rows()
    return from_keys(g.nodes, np.union1d(keys, rev))


def with_self_loops(g: Graph) -> Graph:
    """``A + I``: the pattern with every diagonal entry added."""
    diag = np.arange(g.nodes, dtype=np.int64) * (g.nodes + 1)
    return from_keys(g.nodes, np.union1d(g.keys(), diag))


def generate(traffic: dict) -> Graph:
    """The graph a traffic file describes, drawn from its ``graph_seed``."""
    family = importlib.import_module(f"bench.families.{traffic['family']}")
    g = family.draw(rng_for(traffic["graph_seed"]), **traffic["graph"])
    return symmetrise(g) if traffic.get("symmetric") else g
