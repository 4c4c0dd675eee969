"""Sharded + batched hybrid sparse execution (`shard_map` / `vmap`).

The two scale axes the single-device operators lack:

* :func:`spmm_sharded` / :func:`sddmm_sharded` — run one Libra plan
  split into contiguous-window shards (:mod:`repro.dist.partition`)
  over a named mesh axis with ``shard_map``. Each device runs the
  *existing* single-device fused hybrid apply on its shard; because the
  output is row-partitioned by construction (a window never straddles
  shards), there is **no cross-device combine** — the only collectives
  are on the dense operand (see the halo model below).
* :class:`BatchedSpMM` / :class:`BatchedSDDMM` — apply one plan to a
  ``(batch, k, n)`` stack of dense panels via ``vmap``, compiled once
  per batch shape into a single AOT-cached executable (the serving
  shape: one graph, many feature panels in flight).

Halo model
----------
Each shard's plan columns are remapped onto its *halo* — the
sorted-unique set of dense-operand rows the shard actually touches
(precomputed host-side by the partitioner). At execution time the
device materializes only ``B[halo]`` (one gather), never all of B,
bounding the per-device dense working set by the shard's column
footprint. The dense operand itself can arrive two ways
(``b_layout=`` / ``y_layout=``):

* ``"replicated"`` (default) — every device holds B and gathers its
  halo rows locally; zero communication, memory cost ``O(k·n)`` per
  device.
* ``"rowshard"`` — B rows are sharded over the same mesh axis; the body
  all-gathers the panels over the axis and then halo-compacts. Memory
  cost before compaction is transient; the resident set after the
  gather is still ``O(halo·n)``. (A future point-to-point halo exchange
  can replace the all-gather without touching callers — the halo maps
  already say exactly which rows each device needs.)

Mesh/batch knobs
----------------
``mesh`` + ``axis`` name the shard axis (``mesh.shape[axis]`` must
equal the partition's ``n_shards``); ``backend=`` selects XLA reference
vs Pallas kernels per device; ``edge_vals=`` (SpMM) revalues the plan
from a replicated canonical-nnz value vector inside the body (the
training path — pattern static, values per step). Batched ops take the
batch as the leading axis of the dense stack and cache one executable
per (batch shape, dtype, backend).

Every public entry point here is traceable — it can sit under an outer
``jax.jit`` (the training step) or be AOT-compiled by callers.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api import ExecSpec, resolve_spec
from repro.dist.sharding import auto_axes
from repro.core.spmm import LibraSpMM
from repro.core.sddmm import LibraSDDMM
from repro.kernels import ref
from repro.kernels.ops import (
    _pad_to,
    cached_compile,
    sddmm_apply,
    sddmm_apply_stack,
    spmm_apply,
    spmm_apply_stack,
)
from repro.dist.partition import SDDMMPartition, SpMMPartition, partition_sddmm, partition_spmm

SHARD_AXIS = "shards"
_LAYOUTS = ("replicated", "rowshard")


def place_partition(part, mesh: Mesh, axis: str = SHARD_AXIS):
    """The partition with shard ``i``'s plan resident on device ``i`` of
    the mesh axis, so no apply re-sends plans from one device."""
    sharding = NamedSharding(auto_axes(mesh), P(axis))
    return dataclasses.replace(part, stacked={
        k: jax.device_put(v, sharding) for k, v in part.stacked.items()})


def _local(stacked: dict) -> tuple[dict, jnp.ndarray]:
    """Strip the length-1 shard axis shard_map leaves on each block and
    split off the halo map."""
    local = {k: v[0] for k, v in stacked.items()}
    return local, local.pop("halo")


def spmm_sharded(part: SpMMPartition, b: jnp.ndarray, *, mesh: Mesh,
                 axis: str = SHARD_AXIS, backend: str = "xla",
                 edge_vals: jnp.ndarray | None = None,
                 b_layout: str = "replicated") -> jnp.ndarray:
    """C = A @ B over a mesh axis; each device applies its shard's plan.

    ``edge_vals`` (canonical global nnz order, replicated) revalues
    every shard's plan inside the body — the differentiable-values
    path. Output rows are partitioned by shard, so the result needs no
    reduction: one gather (``part.out_gather``) reassembles C.
    """
    assert b_layout in _LAYOUTS, b_layout
    assert int(mesh.shape[axis]) == part.n_shards, (mesh.shape, part.n_shards)
    rowshard = b_layout == "rowshard"
    if edge_vals is not None and part.edge_perm is not None:
        # Reordered partition: shard plan positions index the reordered
        # canonical nnz order — gather the caller's original-order
        # values (one per head, where they carry heads) into it once,
        # before the replicated broadcast.
        edge_vals = jnp.take(edge_vals, part.edge_perm, axis=0)

    def body(stacked, b_in, *ev):
        local, halo = _local(stacked)
        b_full = (jax.lax.all_gather(b_in, axis, axis=0, tiled=True)
                  if rowshard else b_in)
        b_halo = jnp.take(b_full, halo, axis=0)
        if ev:
            local = ref.revalue_spmm_arrays(local, ev[0])
        out = spmm_apply(local, b_halo, m=part.rows_pad, nwin=part.wmax,
                         backend=backend, cfg=part.run_cfg)
        # Reassemble C from the (P * rows_pad, n) shard panels inside
        # the body, so the gather never indexes a sharded operand.
        out = jax.lax.all_gather(out, axis, axis=0, tiled=True)
        return jnp.take(out, part.out_gather, axis=0)

    spec_plan = {k: P(axis) for k in part.stacked}
    in_specs = [spec_plan, P(axis) if rowshard else P()]
    args = [part.stacked, _pad_to(b, 0, part.n_shards) if rowshard else b]
    if edge_vals is not None:
        in_specs.append(P())
        args.append(edge_vals)
    fn = jax.shard_map(body, mesh=auto_axes(mesh),
                       in_specs=tuple(in_specs), out_specs=P(),
                       check_vma=False)
    return fn(*args)


def sddmm_sharded(part: SDDMMPartition, x: jnp.ndarray, y: jnp.ndarray, *,
                  mesh: Mesh, axis: str = SHARD_AXIS,
                  backend: str = "xla", y_layout: str = "replicated",
                  heads: int | None = None) -> jnp.ndarray:
    """values = sample(X·Yᵀ, sparsity(A)) over a mesh axis, canonical
    global nnz order.

    X is row-sharded to match the output rows (``part.x_take`` lays the
    global rows out in padded per-shard panels before the shard_map);
    Y follows ``y_layout`` like B in :func:`spmm_sharded`. Each shard
    scatters into its local nnz slice; ``part.nnz_gather`` reassembles
    the canonical global vector — again no cross-device combine. With
    ``heads`` = H the values are ``(nnz, H)``, one score per head.
    """
    assert y_layout in _LAYOUTS, y_layout
    assert int(mesh.shape[axis]) == part.n_shards, (mesh.shape, part.n_shards)
    rowshard = y_layout == "rowshard"
    x_panels = jnp.take(x, part.x_take, axis=0)   # (P * rows_pad, kf)

    def body(stacked, x_in, y_in):
        local, halo = _local(stacked)
        y_full = (jax.lax.all_gather(y_in, axis, axis=0, tiled=True)
                  if rowshard else y_in)
        y_halo = jnp.take(y_full, halo, axis=0)
        out = sddmm_apply(local, x_in, y_halo, nnz=part.nnz_pad,
                          backend=backend, cfg=part.run_cfg, heads=heads)
        out = jax.lax.all_gather(out, axis, axis=0, tiled=True)
        return jnp.take(out, part.nnz_gather, axis=0)

    spec_plan = {k: P(axis) for k in part.stacked}
    in_specs = (spec_plan, P(axis), P(axis) if rowshard else P())
    fn = jax.shard_map(body, mesh=auto_axes(mesh), in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(part.stacked, x_panels,
              _pad_to(y, 0, part.n_shards) if rowshard else y)


# ----------------------------------------------------------- batched ---
class BatchedSpMM:
    """Apply one Libra plan to a stack of B panels: ``(batch, k, n) →
    (batch, m, n)`` via ``vmap`` over the single-device fused apply,
    AOT-compiled once per (batch shape, dtype, backend)."""

    def __init__(self, a, spec: ExecSpec | None = None, *, balance=None,
                 **op_kwargs):
        if op_kwargs:
            spec = resolve_spec(spec, "BatchedSpMM", **op_kwargs)
        self.op = LibraSpMM(a, spec=spec, balance=balance)
        self._cache: dict = {}

    def __call__(self, b_stack: jnp.ndarray, backend: str = "xla",
                 edge_vals: jnp.ndarray | None = None) -> jnp.ndarray:
        """Apply the plan to every panel; ``edge_vals`` — optional
        ``(batch, nnz)`` canonical per-panel values — revalues the plan
        per panel (the attention-serving path)."""
        op = self.op
        assert b_stack.ndim == 3 and b_stack.shape[1] == op.k, b_stack.shape
        has_ev = edge_vals is not None
        unperm = op._row_unperm

        def batched(arrs, bb, *ev):
            out = spmm_apply_stack(arrs, bb, m=op.m, nwin=op.nwin,
                                   backend=backend, cfg=op.tune_config,
                                   edge_vals=ev[0] if ev else None)
            if unperm is not None:   # reordered plan: restore row order
                out = jnp.take(out, unperm, axis=1)
            return out

        # Lazy view; with edge_vals the revalue maps replace the
        # baked-in value tensors (rebuilt in-trace per panel).
        arrs = op.arrays.apply_arrays(revalue=has_ev)
        args = (arrs, b_stack) + ((edge_vals,) if has_ev else ())
        fn = cached_compile(
            self._cache,
            (b_stack.shape, str(b_stack.dtype), backend, has_ev),
            lambda: jax.jit(batched).lower(*args))
        return fn(*args)


class BatchedSDDMM:
    """``(batch, m, kf) × (batch, k, kf) → (batch, nnz)`` via ``vmap``
    over the single-device fused apply (one AOT executable per shape)."""

    def __init__(self, a, spec: ExecSpec | None = None, *, balance=None,
                 **op_kwargs):
        if op_kwargs:
            if "threshold" in op_kwargs:
                op_kwargs["sddmm_threshold"] = op_kwargs.pop("threshold")
            spec = resolve_spec(spec, "BatchedSDDMM", **op_kwargs)
        self.op = LibraSDDMM(a, spec=spec, balance=balance)
        self._cache: dict = {}

    def __call__(self, x_stack: jnp.ndarray, y_stack: jnp.ndarray,
                 backend: str = "xla") -> jnp.ndarray:
        op = self.op
        assert x_stack.ndim == 3 and y_stack.ndim == 3
        perm = op._row_perm
        if perm is not None and x_stack.shape[1] > op.m:
            perm = jnp.concatenate(
                [perm, jnp.arange(op.m, x_stack.shape[1])])

        def batched(arrs, xx, yy):
            if perm is not None:   # reordered plan: permute the X rows
                xx = jnp.take(xx, perm, axis=1)
            return sddmm_apply_stack(arrs, xx, yy, nnz=op.nnz,
                                     backend=backend, cfg=op.tune_config)

        arrs = op.arrays.apply_arrays()
        fn = cached_compile(
            self._cache,
            (x_stack.shape, y_stack.shape, str(x_stack.dtype), backend),
            lambda: jax.jit(batched).lower(arrs, x_stack, y_stack))
        return fn(arrs, x_stack, y_stack)


# ----------------------------------------------------------- sharded ops ---
class ShardedSpMM:
    """Engine-callable sharded apply: partition + mesh bound once, one
    AOT executable per dense-operand shape.

    The serving-shape counterpart of :class:`BatchedSpMM` for graphs too
    large (or too imbalanced) for one device: the partition is the
    amortized asset; requests arrive as ``(k, n)`` panels and run the
    ``shard_map`` apply without re-trace/re-jit. Accepts a
    :class:`~repro.dist.partition.SpMMPartition` or a raw
    :class:`~repro.sparse.matrix.SparseCSR` (partitioned here);
    ``edge_vals`` revalues the plan per call (canonical nnz order).
    """

    def __init__(self, a, mesh: Mesh, *, axis: str = SHARD_AXIS,
                 spec: ExecSpec | None = None, **part_kwargs):
        if part_kwargs:
            spec = resolve_spec(spec, "ShardedSpMM", **part_kwargs)
        spec = ExecSpec() if spec is None else spec
        self.spec = spec
        self.part = place_partition(
            a if isinstance(a, SpMMPartition)
            else partition_spmm(a, int(mesh.shape[axis]), spec=spec),
            mesh, axis)
        assert int(mesh.shape[axis]) == self.part.n_shards
        self.mesh, self.axis = mesh, axis
        self.backend, self.b_layout = spec.backend, spec.b_layout
        self.m, self.k, self.nnz = self.part.m, self.part.k, self.part.nnz
        self._cache: dict = {}

    @property
    def tune_config(self):
        return self.part.run_cfg

    def __call__(self, b: jnp.ndarray,
                 edge_vals: jnp.ndarray | None = None) -> jnp.ndarray:
        assert b.shape[0] == self.k, (b.shape, self.k)
        has_ev = edge_vals is not None

        def fn(bb, *ev):
            return spmm_sharded(self.part, bb, mesh=self.mesh,
                                axis=self.axis, backend=self.backend,
                                edge_vals=ev[0] if ev else None,
                                b_layout=self.b_layout)

        args = (b,) + ((edge_vals,) if has_ev else ())
        exe = cached_compile(self._cache, (b.shape, str(b.dtype), has_ev),
                             lambda: jax.jit(fn).lower(*args))
        return exe(*args)


class ShardedSDDMM:
    """Engine-callable sharded SDDMM — see :class:`ShardedSpMM`."""

    def __init__(self, a, mesh: Mesh, *, axis: str = SHARD_AXIS,
                 spec: ExecSpec | None = None, **part_kwargs):
        if part_kwargs:
            if "y_layout" in part_kwargs:
                part_kwargs["b_layout"] = part_kwargs.pop("y_layout")
            if "threshold" in part_kwargs:
                part_kwargs["sddmm_threshold"] = part_kwargs.pop("threshold")
            spec = resolve_spec(spec, "ShardedSDDMM", **part_kwargs)
        spec = ExecSpec() if spec is None else spec
        self.spec = spec
        self.part = place_partition(
            a if isinstance(a, SDDMMPartition)
            else partition_sddmm(a, int(mesh.shape[axis]), spec=spec),
            mesh, axis)
        assert int(mesh.shape[axis]) == self.part.n_shards
        self.mesh, self.axis = mesh, axis
        self.backend, self.y_layout = spec.backend, spec.b_layout
        self.m, self.k, self.nnz = self.part.m, self.part.k, self.part.nnz
        self._cache: dict = {}

    @property
    def tune_config(self):
        return self.part.run_cfg

    def __call__(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        assert x.shape[0] >= self.m and y.shape[0] >= self.k

        def fn(xx, yy):
            return sddmm_sharded(self.part, xx, yy, mesh=self.mesh,
                                 axis=self.axis, backend=self.backend,
                                 y_layout=self.y_layout)

        exe = cached_compile(self._cache,
                             (x.shape, y.shape, str(x.dtype)),
                             lambda: jax.jit(fn).lower(x, y))
        return exe(x, y)
