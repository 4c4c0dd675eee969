"""Floating-point operations a training step requires, from the layer
equations and the graph's sizes alone.

A count never looks at a plan or at how an operator is implemented, so it
stays the same when a later change moves work between the MXU and VPU
streams, pads differently or drops an operator. A multiply-add counts as
two operations. Only products are counted: dense matrix products, SpMM
(``2 · edges · width``) and SDDMM (``2 · edges · width``); element-wise
work (activations, normalisation, softmax, the loss) is left out, as
model-FLOP counts conventionally do. Gradients that no parameter needs
are not counted: the input features', and the fixed GCN edge
normalisation's.
"""
from __future__ import annotations


def dense(nodes: int, d_in: int, d_out: int) -> int:
    """``(nodes × d_in) @ (d_in × d_out)``."""
    return 2 * nodes * d_in * d_out


def spmm(edges: int, width: int) -> int:
    """``A @ B`` with ``edges`` non-zeros and ``B`` of ``width`` columns."""
    return 2 * edges * width


def sddmm(edges: int, width: int) -> int:
    """``⟨X[row], Y[col]⟩`` at each of ``edges`` positions."""
    return 2 * edges * width


def gcn_step(nodes: int, edges: int, dims: list[int]) -> int:
    """One SGD step of a GCN, ``H' = Â (H W)`` per layer.

    Forward, per layer: ``H W`` and the SpMM by ``Â``. Backward, per
    layer: the SpMM by ``Âᵀ`` of the output gradient, the weight gradient
    ``Hᵀ dZ``, and the input gradient ``dZ Wᵀ`` except in the first layer,
    whose input is the features.
    """
    total = 0
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        total += dense(nodes, d_in, d_out) + spmm(edges, d_out)    # forward
        total += spmm(edges, d_out) + dense(nodes, d_in, d_out)    # Âᵀ, dW
        if i > 0:
            total += dense(nodes, d_in, d_out)                     # dH
    return total


def agnn_step(nodes: int, edges: int, dims: list[int]) -> int:
    """One SGD step of AGNN, per layer: ``S = β · SDDMM(Ĥ, Ĥ)`` on the
    row-normalised ``Ĥ``, ``P = softmax_row(S)``, ``Z = (P H) W``.

    Forward, per layer: the SDDMM, the SpMM ``P H`` and the product by
    ``W``. Backward, per layer: ``dW``; ``d(P H) = dZ Wᵀ`` and the SDDMM
    ``dP`` (needed in every layer, since ``β`` is a parameter of each);
    and, except in the first layer, the input gradient: the SpMM ``Pᵀ dZ``
    and the SDDMM's two SpMMs into ``Ĥ``.
    """
    total = 0
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        total += (sddmm(edges, d_in) + spmm(edges, d_in)
                  + dense(nodes, d_in, d_out))                     # forward
        total += (2 * dense(nodes, d_in, d_out)                    # dW, dPH
                  + sddmm(edges, d_in))                            # dP
        if i > 0:
            total += 3 * spmm(edges, d_in)                         # dH
    return total
