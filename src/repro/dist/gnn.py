"""Multi-device GNN training on sharded Libra ops.

:class:`DistGraphOps` mirrors :class:`repro.models.gnn.GraphOps` —
same differentiable ``spmm``/``sddmm`` surface, same gradient duality —
but every apply (forward *and* both VJP legs) runs through the
``shard_map`` ops in :mod:`repro.dist.sparse` on a device mesh. The
model code is unchanged: ``gcn_forward`` / ``agnn_forward`` /
``unimp_forward`` / ``edge_softmax`` from :mod:`repro.models.gnn`
duck-type over either ops object, so going multi-device is a one-line
swap. Multi-head edge values, ``(nnz, H)``, ride through the same
sharded applies.

Partitions built once per graph (paper §4.5 — preprocess-once,
apply-many, now shard-once too): A for the forward SpMM, Aᵀ for the
feature-gradient SpMM, and SDDMM(A) for the value gradient. The edge
permutation between A's and Aᵀ's canonical nnz orders is the same
host-side map the single-device path uses.

Unlike :class:`GraphOps` (``tune="off"`` default, kept cheap and
backward compatible), ``DistGraphOps`` defaults to ``tune="model"`` —
per-*shard* analytical tuning is the point of partitioned execution,
and its cost is one feature pass per shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.api import UNSET, ExecSpec, resolve_spec
from repro.dist.partition import partition_sddmm, partition_spmm
from repro.dist.sparse import (SHARD_AXIS, place_partition, sddmm_sharded,
                               spmm_sharded)
from repro.models.gnn import (call_spans, edge_heads, edge_softmax,
                              gcn_forward, transpose_csr)
from repro.sparse.matrix import SparseCSR


class DistGraphOps:
    """Sharded Libra plans for one graph: A, Aᵀ, and SDDMM(A) on a mesh.

    Drop-in for :class:`repro.models.gnn.GraphOps` in model code.
    ``tune="model"`` (default — see module docstring) tunes every shard
    of every partition; ``backend=``/``b_layout=`` select the per-device
    apply path and the dense-operand placement for all ops.
    """

    def __init__(self, a: SparseCSR, mesh: Mesh, axis: str = SHARD_AXIS,
                 mode=UNSET, spmm_threshold=UNSET, sddmm_threshold=UNSET,
                 tune=UNSET, backend=UNSET, b_layout=UNSET, *,
                 spec: ExecSpec | None = None):
        # ExecSpec's tune default ("model") matches this class's legacy
        # default, so the spec-less path is unchanged. Reordering
        # (spec.reorder) rides inside the partitions: their gathers are
        # pre-composed with the permutations, so the VJP legs below
        # stay original-order black boxes.
        spec = resolve_spec(
            spec, "DistGraphOps", mode=mode, threshold=spmm_threshold,
            sddmm_threshold=sddmm_threshold, tune=tune, backend=backend,
            b_layout=b_layout)
        self.spec = spec
        self.mesh, self.axis = mesh, axis
        self.backend, self.b_layout = spec.backend, spec.b_layout
        self.a = a
        self.m, self.k = a.shape
        self.nnz = a.nnz
        n_shards = int(mesh.shape[axis])
        at, self.perm = transpose_csr(a)
        self.part, self.part_t, self.part_sd = (
            place_partition(p, mesh, axis) for p in (
                partition_spmm(a, n_shards, spec=spec),
                partition_spmm(at, n_shards, spec=spec),
                partition_sddmm(a, n_shards, spec=spec)))
        self.perm_dev = jnp.asarray(self.perm)
        rows, _, _ = a.to_coo()
        self.edge_row = jnp.asarray(rows, jnp.int32)
        self.edge_col = jnp.asarray(a.indices, jnp.int32)

    # -- differentiable ops (same surface as GraphOps) --------------------
    def spmm(self, edge_vals, b):
        """C = A(edge_vals) @ B, differentiable in (edge_vals, b)."""
        return _dist_spmm_ev(self, edge_vals, b)

    def sddmm(self, x, y, heads: int | None = None):
        """vals[p] = ⟨X[row_p], Y[col_p]⟩, differentiable in (x, y); with
        ``heads`` = H, one score per head, ``(nnz, H)``."""
        return _dist_sddmm_ev(self, x, y, heads)

    def fixed_spmm(self, b):
        """C = A @ B with the plans' baked-in values (no value grads)."""
        return self._spmm(self.part, b)

    # -- sharded applies with this object's mesh/backend knobs ------------
    # Each apply runs under the operator's named scope, as on one device
    # (repro.models.gnn).
    def _spmm(self, part, b, edge_vals=None):
        heads = None if edge_vals is None else edge_heads(edge_vals)
        with call_spans("spmm", heads, b.shape[1], part.run_cfg), \
                jax.named_scope("spmm"):
            return spmm_sharded(part, b, mesh=self.mesh, axis=self.axis,
                                backend=self.backend, edge_vals=edge_vals,
                                b_layout=self.b_layout)

    def _sddmm(self, x, y, heads=None):
        with call_spans("sddmm", heads, x.shape[1], self.part_sd.run_cfg), \
                jax.named_scope("sddmm"):
            return sddmm_sharded(self.part_sd, x, y, mesh=self.mesh,
                                 axis=self.axis, backend=self.backend,
                                 y_layout=self.b_layout, heads=heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dist_spmm_ev(g: DistGraphOps, edge_vals, b):
    return g._spmm(g.part, b, edge_vals=edge_vals)


def _dist_spmm_ev_fwd(g, edge_vals, b):
    return _dist_spmm_ev(g, edge_vals, b), (edge_vals, b)


def _dist_spmm_ev_bwd(g, resid, d_c):
    edge_vals, b = resid
    # dB = A(v)ᵀ @ dC — sharded SpMM on the transposed partition.
    d_b = g._spmm(g.part_t, d_c, edge_vals=edge_vals[g.perm_dev])
    # dv[p] = dC[row_p] · B[col_p] — sharded SDDMM with A's sparsity.
    d_vals = g._sddmm(d_c, b, edge_heads(edge_vals))
    return d_vals.astype(edge_vals.dtype), d_b.astype(b.dtype)


_dist_spmm_ev.defvjp(_dist_spmm_ev_fwd, _dist_spmm_ev_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _dist_sddmm_ev(g: DistGraphOps, x, y, heads):
    return g._sddmm(x, y, heads)


def _dist_sddmm_ev_fwd(g, x, y, heads):
    return _dist_sddmm_ev(g, x, y, heads), (x, y)


def _dist_sddmm_ev_bwd(g, heads, resid, d_vals):
    x, y = resid
    # dX = A(dv) @ Y ; dY = A(dv)ᵀ @ X — both sharded SpMMs.
    d_x = g._spmm(g.part, y, edge_vals=d_vals)
    d_y = g._spmm(g.part_t, x, edge_vals=d_vals[g.perm_dev])
    return d_x.astype(x.dtype), d_y.astype(y.dtype)


_dist_sddmm_ev.defvjp(_dist_sddmm_ev_fwd, _dist_sddmm_ev_bwd)


# ------------------------------------------------------- training steps ---
def gcn_loss(params, g, feats, labels, norm_edge_vals):
    """Cross-entropy of a GCN forward over either ops object."""
    logits = gcn_forward(params, g, feats, norm_edge_vals)
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()


def make_gcn_train_step(g, lr: float = 0.2):
    """Jitted SGD step: works with GraphOps (single-device) and
    DistGraphOps (mesh) alike — the mesh rides inside the sharded ops.
    Its jitted name, ``gcn_train_step``, names the step's module in a
    profile."""
    @jax.jit
    def gcn_train_step(params, feats, labels, norm_edge_vals):
        loss, grads = jax.value_and_grad(gcn_loss)(
            params, g, feats, labels, norm_edge_vals)
        new = jax.tree.map(lambda p, gg: p - lr * gg, params, grads)
        return new, loss
    return gcn_train_step


def agnn_loss(params, g, feats, labels):
    from repro.models.gnn import agnn_forward

    logits = agnn_forward(params, g, feats)
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()


def make_agnn_train_step(g, lr: float = 0.2):
    """Jitted SGD step for AGNN (SDDMM → edge softmax → SpMM per layer),
    named ``agnn_train_step`` in a profile."""
    @jax.jit
    def agnn_train_step(params, feats, labels):
        loss, grads = jax.value_and_grad(agnn_loss)(params, g, feats, labels)
        new = jax.tree.map(lambda p, gg: p - lr * gg, params, grads)
        return new, loss
    return agnn_train_step


def unimp_loss(params, g, feats, labels):
    from repro.models.gnn import unimp_forward

    logits = unimp_forward(params, g, feats)
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()


def make_unimp_train_step(g, lr: float = 0.2):
    """Jitted SGD step for UniMP (per layer one multi-head SDDMM → edge
    softmax → SpMM, heads fused), named ``unimp_train_step`` in a
    profile."""
    @jax.jit
    def unimp_train_step(params, feats, labels):
        loss, grads = jax.value_and_grad(unimp_loss)(params, g, feats,
                                                     labels)
        new = jax.tree.map(lambda p, gg: p - lr * gg, params, grads)
        return new, loss
    return unimp_train_step


__all__ = [
    "DistGraphOps",
    "agnn_loss",
    "edge_softmax",
    "gcn_loss",
    "make_agnn_train_step",
    "make_gcn_train_step",
    "make_unimp_train_step",
    "unimp_loss",
]
