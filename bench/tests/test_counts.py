"""Required FLOPs per step, against counts made by hand."""
from bench import counts


def test_gcn_toy_graph_by_hand():
    # 4 nodes, 6 edges, widths 3 -> 5 -> 2.
    # Layer 1: X W 2*4*3*5=120, Â 2*6*5=60; Âᵀ 60, dW 120; no dX.
    # Layer 2: H W 2*4*5*2=80, Â 2*6*2=24; Âᵀ 24, dW 80, dH 80.
    assert counts.gcn_step(4, 6, [3, 5, 2]) == 360 + 288


def test_agnn_toy_graph_by_hand():
    # Layer 1 (width 3): SDDMM 36, P X 36, ·W 120; dW 120, d(PX) 120,
    # dP 36; no dX.
    # Layer 2 (width 5): SDDMM 60, P H 60, ·W 80; dW 80, d(PH) 80, dP 60;
    # dH: Pᵀ dZ 60 and the SDDMM's two SpMMs 120.
    assert counts.agnn_step(4, 6, [3, 5, 2]) == 468 + 600


def test_counts_depend_on_sizes_only():
    # ogbn-arxiv at the configurations' widths (PERF.md's figures).
    assert counts.gcn_step(169_343, 1_166_243, [128, 256, 256, 40]) == \
        101_764_001_248
    assert counts.agnn_step(169_343, 1_166_243, [128, 256, 40]) == \
        48_176_995_584
    # Twice the edges add exactly the sparse terms again.
    n, e, d = 1000, 5000, [16, 32, 4]
    sparse = counts.gcn_step(n, 2 * e, d) - counts.gcn_step(n, e, d)
    assert sparse == sum(2 * counts.spmm(e, w) for w in d[1:])
