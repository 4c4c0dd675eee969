"""GNN models (GCN, AGNN) on Libra hybrid sparse operators.

This is the paper's end-to-end application (§5.5): SpMM performs feature
aggregation, SDDMM computes per-edge attention. Gradients follow the
classic duality — the VJP of a value-parameterized SpMM is an SpMM with
the transposed plan (for features) plus an SDDMM with the same sparsity
(for edge values) — so *every* matmul in training runs through Libra ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import UNSET, ExecSpec, resolve_spec
from repro.core import preprocess
from repro.core.formats import device_arrays
from repro.core.windows import num_windows
from repro.kernels import ref
from repro.kernels.ops import sddmm_apply, spmm_apply
from repro.sparse.matrix import SparseCSR, coo_to_csr


def transpose_csr(a: SparseCSR) -> tuple[SparseCSR, np.ndarray]:
    """A^T plus the permutation mapping A's nnz order → A^T's nnz order."""
    rows, cols, vals = a.to_coo()
    at = coo_to_csr(a.k, a.m, cols, rows, vals)
    # Position of each A-edge inside A^T's canonical (row-major on cols) order.
    order = np.lexsort((rows, cols))  # A^T canonical order over A's edges
    perm = np.asarray(order, np.int32)  # edge p_T of A^T = A-edge perm[p_T]
    return at, perm


class GraphOps:
    """Preprocessed Libra plans for one graph: A, A^T, and SDDMM(A).

    All three legs are built through the canonical
    :meth:`repro.core.preprocess.Plan.build` pipeline under one frozen
    :class:`repro.api.ExecSpec` (``spec=``; the legacy kwargs keep
    working via the deprecation shim — ``spmm_threshold`` maps to
    ``ExecSpec.threshold``, ``sddmm_threshold`` to
    ``ExecSpec.sddmm_threshold``). For backward compatibility the
    spec-less default stays ``tune="off"`` (cheap construction);
    ``tune="model"`` — recommended for real training runs, and the
    default on :class:`repro.dist.DistGraphOps` — picks per-graph
    thresholds and tile sizes analytically (A and Aᵀ each get their own
    config — their sparsity patterns differ).

    ``backend`` selects the apply path for *every* op in the training
    graph, forward and backward: ``"xla"`` (default) runs the jnp
    reference, ``"pallas"`` the TPU kernels (compiled on a TPU, the
    Pallas interpreter elsewhere).
    The tuned configs are threaded into each apply, so a tuned operator
    trains through the exact plan the tuner priced.

    ``spec.reorder`` densifies each leg independently (A, Aᵀ and the
    SDDMM mask each get their own row permutation priced on their own
    pattern); every leg stays an original-order-in/original-order-out
    black box — its plan's nnz maps are rewritten to its matrix's
    canonical order at build time and the row permutes ride inside the
    differentiable applies — so edge values, the Aᵀ edge permutation
    and the softmax segment ids never change.
    """

    def __init__(self, a: SparseCSR, mode=UNSET, spmm_threshold=UNSET,
                 sddmm_threshold=UNSET, tune=UNSET, backend=UNSET,
                 reorder=UNSET, *, spec=None):
        base = spec if spec is not None else ExecSpec(tune="off")
        spec = resolve_spec(base, "GraphOps", mode=mode,
                            threshold=spmm_threshold,
                            sddmm_threshold=sddmm_threshold, tune=tune,
                            backend=backend, reorder=reorder)
        from repro.tune import matrix_features

        self.spec = spec
        self.a = a
        self.m, self.k = a.shape
        self.nnz = a.nnz
        self.backend = spec.backend
        self.nwin = num_windows(a.m)
        at, self.perm = transpose_csr(a)
        self.nwin_t = num_windows(at.m)
        # One feature pass per matrix, shared by the SpMM and SDDMM tuners.
        feat_a = matrix_features(a) if spec.tune == "model" else None
        built = preprocess.Plan.build(a, "spmm", spec, feat=feat_a)
        built_t = preprocess.Plan.build(at, "spmm", spec)
        built_sd = preprocess.Plan.build(a, "sddmm", spec, feat=feat_a)
        self.cfg, self.cfg_t = built.cfg, built_t.cfg
        self.cfg_sd = built_sd.cfg
        self.arrs = device_arrays(built.plan)
        self.arrs_t = device_arrays(built_t.plan)
        self.arrs_sd = device_arrays(built_sd.plan)
        # Per-leg reorder epilogues/prologues (None when not reordered):
        # the plans' nnz maps already point at each leg's own original
        # canonical order, so values flow unchanged — only rows permute.
        self._unperm = (None if built.reorder is None
                        else jnp.asarray(built.reorder.row_inv))
        self._unperm_t = (None if built_t.reorder is None
                          else jnp.asarray(built_t.reorder.row_inv))
        self._x_perm = (None if built_sd.reorder is None
                        else jnp.asarray(built_sd.reorder.row_perm))
        self.perm_dev = jnp.asarray(self.perm)
        # Row id per edge (for softmax over incident edges).
        rows, _, _ = a.to_coo()
        self.edge_row = jnp.asarray(rows, jnp.int32)
        self.edge_col = jnp.asarray(a.indices, jnp.int32)

    # -- differentiable ops ------------------------------------------------
    def spmm(self, edge_vals, b):
        """C = A(edge_vals) @ B, differentiable in (edge_vals, b)."""
        return _spmm_ev(self, edge_vals, b)

    def sddmm(self, x, y):
        """vals[p] = ⟨X[row_p], Y[col_p]⟩, differentiable in (x, y)."""
        return _sddmm_ev(self, x, y)

    def fixed_spmm(self, b, backend: str | None = None):
        """C = A @ B with the plan's baked-in values (no grad wrt values)."""
        out = spmm_apply(self.arrs, b, m=self.m, nwin=self.nwin,
                         backend=backend or self.backend, cfg=self.cfg)
        return _unreorder(out, self._unperm)


def _unreorder(out, unperm):
    """Restore original row order after a reordered-plan SpMM apply."""
    return out if unperm is None else jnp.take(out, unperm, axis=0)


def _reorder_x(x, perm):
    """Gather X into the reordered row space of a reordered SDDMM plan."""
    return x if perm is None else jnp.take(x, perm, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_ev(g: GraphOps, edge_vals, b):
    arrs = ref.revalue_spmm_arrays(g.arrs, edge_vals)
    out = spmm_apply(arrs, b, m=g.m, nwin=g.nwin, backend=g.backend,
                     cfg=g.cfg)
    return _unreorder(out, g._unperm)


def _spmm_ev_fwd(g, edge_vals, b):
    return _spmm_ev(g, edge_vals, b), (edge_vals, b)


def _spmm_ev_bwd(g, resid, d_c):
    edge_vals, b = resid
    # dB = A(v)^T @ dC — SpMM on the transposed plan with permuted values.
    arrs_t = ref.revalue_spmm_arrays(g.arrs_t, edge_vals[g.perm_dev])
    d_b = _unreorder(
        spmm_apply(arrs_t, d_c, m=g.k, nwin=g.nwin_t, backend=g.backend,
                   cfg=g.cfg_t), g._unperm_t)
    # dv[p] = dC[row_p] · B[col_p] — SDDMM with A's sparsity.
    d_vals = sddmm_apply(g.arrs_sd, _reorder_x(d_c, g._x_perm), b,
                         nnz=g.nnz, backend=g.backend, cfg=g.cfg_sd)
    return d_vals.astype(edge_vals.dtype), d_b.astype(b.dtype)


_spmm_ev.defvjp(_spmm_ev_fwd, _spmm_ev_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sddmm_ev(g: GraphOps, x, y):
    return sddmm_apply(g.arrs_sd, _reorder_x(x, g._x_perm), y, nnz=g.nnz,
                       backend=g.backend, cfg=g.cfg_sd)


def _sddmm_ev_fwd(g, x, y):
    return _sddmm_ev(g, x, y), (x, y)


def _sddmm_ev_bwd(g, resid, d_vals):
    x, y = resid
    # dX = A(dv) @ Y ; dY = A(dv)^T @ X — both SpMMs through Libra plans.
    arrs = ref.revalue_spmm_arrays(g.arrs, d_vals)
    d_x = _unreorder(
        spmm_apply(arrs, y, m=g.m, nwin=g.nwin, backend=g.backend,
                   cfg=g.cfg), g._unperm)
    arrs_t = ref.revalue_spmm_arrays(g.arrs_t, d_vals[g.perm_dev])
    d_y = _unreorder(
        spmm_apply(arrs_t, x, m=g.k, nwin=g.nwin_t, backend=g.backend,
                   cfg=g.cfg_t), g._unperm_t)
    return d_x.astype(x.dtype), d_y.astype(y.dtype)


_sddmm_ev.defvjp(_sddmm_ev_fwd, _sddmm_ev_bwd)


def edge_softmax(g: GraphOps, scores):
    """Numerically stable per-destination-row softmax over edge scores."""
    mx = jax.ops.segment_max(scores, g.edge_row, num_segments=g.m)
    e = jnp.exp(scores - mx[g.edge_row])
    z = jax.ops.segment_sum(e, g.edge_row, num_segments=g.m)
    return e / jnp.maximum(z[g.edge_row], 1e-9)


# ------------------------------------------------------------------ GCN ---
def init_gcn(rng, dims: list[int]):
    keys = jax.random.split(rng, len(dims) - 1)
    return [
        {"w": jax.random.normal(k, (dims[i], dims[i + 1])) / np.sqrt(dims[i])}
        for i, k in enumerate(keys)
    ]


def gcn_forward(params, g: GraphOps, x, norm_edge_vals):
    """GCN: H' = σ(Â H W); Â's normalized values are the edge values."""
    h = x
    for i, lp in enumerate(params):
        h = g.spmm(norm_edge_vals, h @ lp["w"])
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def gcn_norm_edges(a: SparseCSR) -> np.ndarray:
    """Symmetric normalization D^-1/2 A D^-1/2 as per-edge values."""
    rows, cols, _ = a.to_coo()
    deg = np.maximum(np.bincount(rows, minlength=a.m), 1).astype(np.float64)
    deg_c = np.maximum(np.bincount(cols, minlength=a.k), 1).astype(np.float64)
    return (1.0 / np.sqrt(deg[rows] * deg_c[cols])).astype(np.float32)


# ----------------------------------------------------------------- AGNN ---
def init_agnn(rng, dims: list[int]):
    keys = jax.random.split(rng, len(dims) - 1)
    layers = [
        {"w": jax.random.normal(k, (dims[i], dims[i + 1])) / np.sqrt(dims[i]),
         "beta": jnp.ones(())}
        for i, k in enumerate(keys)
    ]
    return layers


def agnn_forward(params, g: GraphOps, x):
    """AGNN: attention = softmax_row(β·cos(h_i, h_j)) via SDDMM, then SpMM."""
    h = x
    for i, lp in enumerate(params):
        hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-9)
        scores = g.sddmm(hn, hn) * lp["beta"]          # SDDMM (paper Fig. 3)
        att = edge_softmax(g, scores)
        h = g.spmm(att, h)                             # SpMM aggregation
        h = h @ lp["w"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h
