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
from repro.obs.trace import get_tracer
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

    Under an enabled :mod:`repro.obs.trace` tracer the build is one
    ``graphops.build`` span whose children cover it: ``graphops.transpose``,
    ``graphops.features``, one ``graphops.leg`` per plan (attribute
    ``leg`` = ``spmm``, ``spmm_t`` or ``sddmm``; it holds that leg's
    tune, preprocess and upload wrapping) and ``graphops.edges``.
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
        tr = get_tracer()
        with tr.span("graphops.build", m=a.m, k=a.k, nnz=a.nnz):
            with tr.span("graphops.transpose"):
                at, self.perm = transpose_csr(a)
            self.nwin_t = num_windows(at.m)
            # One feature pass per matrix, shared by the SpMM and SDDMM
            # tuners.
            with tr.span("graphops.features"):
                feat_a = matrix_features(a) if spec.tune == "model" else None
            # Per-leg reorder epilogues/prologues (None when not
            # reordered): the plans' nnz maps already point at each leg's
            # own original canonical order, so values flow unchanged —
            # only rows permute.
            built, self.arrs, self._unperm = _build_leg(
                tr, "spmm", a, spec, feat_a)
            built_t, self.arrs_t, self._unperm_t = _build_leg(
                tr, "spmm_t", at, spec, None)
            built_sd, self.arrs_sd, self._x_perm = _build_leg(
                tr, "sddmm", a, spec, feat_a)
            self.cfg, self.cfg_t = built.cfg, built_t.cfg
            self.cfg_sd = built_sd.cfg
            with tr.span("graphops.edges"):
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
        with jax.named_scope("spmm"):
            out = spmm_apply(self.arrs, b, m=self.m, nwin=self.nwin,
                             backend=backend or self.backend, cfg=self.cfg)
            return _unreorder(out, self._unperm)


def _build_leg(tr, leg: str, a: SparseCSR, spec, feat):
    """One plan of a :class:`GraphOps` under its ``graphops.leg`` span:
    the built plan, its device arrays and its row permutation on the
    device (the inverse for an SpMM leg, the forward one for SDDMM;
    ``None`` when not reordered)."""
    op = "sddmm" if leg == "sddmm" else "spmm"
    with tr.span("graphops.leg", leg=leg):
        built = preprocess.Plan.build(a, op, spec, feat=feat)
        arrs = device_arrays(built.plan)
        perm = None
        if built.reorder is not None:
            perm = jnp.asarray(built.reorder.row_perm if op == "sddmm"
                               else built.reorder.row_inv)
        return built, arrs, perm


def _unreorder(out, unperm):
    """Restore original row order after a reordered-plan SpMM apply."""
    return out if unperm is None else jnp.take(out, unperm, axis=0)


def _reorder_x(x, perm):
    """Gather X into the reordered row space of a reordered SDDMM plan."""
    return x if perm is None else jnp.take(x, perm, axis=0)


# Each sparse operator of the training step runs under one named scope —
# ``spmm``, ``sddmm`` or ``edge_softmax`` — so a profile attributes every
# device op to the operator that issued it. The scopes wrap each apply,
# never a custom-VJP rule as a whole, so they never nest in one another;
# ``spmm_apply``/``sddmm_apply`` split them further (``mxu``, ``vpu``,
# ``combine``).
def _spmm(g: GraphOps, edge_vals, b, *, transposed: bool = False):
    """``A(edge_vals) @ b``, or ``A(edge_vals)ᵀ @ b`` through Aᵀ's plan
    (``edge_vals`` in A's order), under the ``spmm`` scope: the values
    gathered into the plan's slots (``revalue``), the apply, and the row
    order restored (``combine``)."""
    arrs, m, nwin, cfg, unperm = (
        (g.arrs_t, g.k, g.nwin_t, g.cfg_t, g._unperm_t) if transposed
        else (g.arrs, g.m, g.nwin, g.cfg, g._unperm))
    with jax.named_scope("spmm"):
        with jax.named_scope("revalue"):
            if transposed:
                edge_vals = edge_vals[g.perm_dev]
            arrs = ref.revalue_spmm_arrays(arrs, edge_vals)
        out = spmm_apply(arrs, b, m=m, nwin=nwin, backend=g.backend,
                         cfg=cfg)
        with jax.named_scope("combine"):
            return _unreorder(out, unperm)


def _sddmm(g: GraphOps, x, y):
    """``vals[p] = ⟨x[row_p], y[col_p]⟩`` under the ``sddmm`` scope."""
    with jax.named_scope("sddmm"):
        return sddmm_apply(g.arrs_sd, _reorder_x(x, g._x_perm), y,
                           nnz=g.nnz, backend=g.backend, cfg=g.cfg_sd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_ev(g: GraphOps, edge_vals, b):
    return _spmm(g, edge_vals, b)


def _spmm_ev_fwd(g, edge_vals, b):
    return _spmm_ev(g, edge_vals, b), (edge_vals, b)


def _spmm_ev_bwd(g, resid, d_c):
    edge_vals, b = resid
    # dB = A(v)^T @ dC — SpMM on the transposed plan with permuted values.
    d_b = _spmm(g, edge_vals, d_c, transposed=True)
    # dv[p] = dC[row_p] · B[col_p] — SDDMM with A's sparsity.
    d_vals = _sddmm(g, d_c, b)
    return d_vals.astype(edge_vals.dtype), d_b.astype(b.dtype)


_spmm_ev.defvjp(_spmm_ev_fwd, _spmm_ev_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sddmm_ev(g: GraphOps, x, y):
    return _sddmm(g, x, y)


def _sddmm_ev_fwd(g, x, y):
    return _sddmm_ev(g, x, y), (x, y)


def _sddmm_ev_bwd(g, resid, d_vals):
    x, y = resid
    # dX = A(dv) @ Y ; dY = A(dv)^T @ X — both SpMMs through Libra plans.
    d_x = _spmm(g, d_vals, y)
    d_y = _spmm(g, d_vals, x, transposed=True)
    return d_x.astype(x.dtype), d_y.astype(y.dtype)


_sddmm_ev.defvjp(_sddmm_ev_fwd, _sddmm_ev_bwd)


def edge_softmax(g: GraphOps, scores):
    """Numerically stable per-destination-row softmax over edge scores."""
    with jax.named_scope("edge_softmax"):
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
