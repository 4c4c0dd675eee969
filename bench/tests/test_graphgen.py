"""The graph generator meets the traffic files' exact counts."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def assert_simple(g):
    """CSR of distinct, sorted, in-range edges with no self loops."""
    assert g.indptr[0] == 0 and g.indptr[-1] == g.edges
    assert np.all(np.diff(g.indptr) >= 0)
    rows, cols = g.rows(), g.indices
    assert cols.min() >= 0 and cols.max() < g.nodes
    assert not np.any(rows == cols)
    keys = rows.astype(np.int64) * g.nodes + cols
    assert np.all(np.diff(keys) > 0)      # sorted and no duplicates


def family_draw(traffic):
    """The traffic's graph as its family draws it, before symmetrising."""
    return graphgen.generate(dict(traffic, symmetric=False))


@pytest.mark.parametrize("name", ["arxiv"])
@pytest.mark.parametrize("seed", [None, 2**31 + 12345])
def test_published_counts_exactly(name, seed):
    traffic = load(name)
    if seed is not None:
        traffic["graph_seed"] = seed
    g = family_draw(traffic)
    assert g.nodes == traffic["published"]["nodes"]
    assert g.edges == traffic["published"]["edges"]
    assert_simple(g)


def test_symmetric_arxiv_is_the_union_with_its_reverse():
    traffic = load("arxiv")
    assert traffic["symmetric"]
    directed = family_draw(traffic)
    g = graphgen.generate(traffic)
    assert_simple(g)
    fwd = g.keys()
    back = np.sort(g.indices.astype(np.int64) * g.nodes + g.rows())
    assert np.array_equal(fwd, back)
    assert np.all(np.isin(directed.keys(), fwd))
    assert g.edges <= 2 * directed.edges
    assert g.edges > 1.95 * directed.edges


def test_self_loops_add_the_diagonal_once():
    g = graphgen.generate({
        "family": "power_law", "graph_seed": 5, "symmetric": True,
        "graph": {"nodes": 300, "edges": 2000, "pareto_shape": 1.5}})
    h = graphgen.with_self_loops(g)
    assert h.edges == g.edges + g.nodes
    rows, cols = h.rows(), h.indices
    assert np.flatnonzero(rows == cols).size == g.nodes
    assert np.all(np.diff(h.keys()) > 0)
    assert graphgen.with_self_loops(h).edges == h.edges


def test_a_family_is_found_by_module_name():
    with pytest.raises(ModuleNotFoundError):
        graphgen.generate({"family": "no_such_family", "graph_seed": 0,
                           "graph": {}})


def test_same_seed_same_graph_and_seeds_apart():
    def draw(seed):
        return graphgen.generate({
            "family": "power_law", "graph_seed": seed,
            "graph": {"nodes": 500, "edges": 3000, "pareto_shape": 1.5}})

    a, b, c, d = draw(2**40 + 1), draw(2**40 + 1), draw(1), draw(-1)
    assert np.array_equal(a.indices, b.indices)
    assert not np.array_equal(a.indices, c.indices)
    assert d.edges == 3000
    assert_simple(d)
