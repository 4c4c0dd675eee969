"""Jit'd wrappers around the Pallas kernels + the single-pass hybrid combine.

``backend="pallas"`` runs the TPU kernels (compiled on a TPU; the Pallas
interpreter elsewhere — the CPU correctness substrate); ``backend="xla"``
runs the pure-jnp oracles from :mod:`repro.kernels.ref` (the fast path on
CPU and the baseline the kernels are validated against). All padding
(N → multiple of the lane tile, features → multiple of the feature tile,
M → multiple of the window) happens here so kernels stay hardware-aligned
(MXU multiples of 128 lanes / 8 sublanes).

Kernel architecture (single-pass fused hybrid)
----------------------------------------------

The hybrid overhead the paper drives to zero (§4.4–4.5) is re-introduced
whenever the two streams materialize redundant output or combine in extra
passes:

1. **Id-driven row fetch.** The dense operands stay in HBM; every grid
   step DMAs exactly the rows its column/row ids name into VMEM
   (:mod:`repro.kernels.gather`), so operand traffic scales with the
   plan's padded non-zeros, not with ``k`` — there is no k-panel sweep.
2. **Tuned tiling.** Every segment-cap decision and the lane-tile caps
   arrive as one static :class:`repro.tune.model.TuneConfig` — emitted
   by the occupancy-aware tuner in :mod:`repro.tune` (or its defaults
   when callers pass nothing). No module constants. Each apply derives
   its lane tile from the operand's width under those caps
   (:func:`repro.tune.model.lane_tile`), so a call copies each dense
   row once where they allow, not once per 128 lanes. With several
   lane tiles SpMM runs ``block_outer``, which fetches each condensed
   TC block once instead of once per lane tile; with one, both grid
   orders run the same steps.
3. **Fused combine epilogue.** The TC partials sum into their windows
   of C and the row-sorted VPU partials scatter-add over them — the
   TPU-deterministic analogue of the paper's atomicAdd combine. Both are
   sorted scatters (plans keep windows and rows non-decreasing). SDDMM
   combines both streams' scores with a single scatter into the
   canonical nnz vector (multi-head: a gather by the inverse map).
4. **Segment-granular launch (§4.3).** A plan's device arrays are the
   hybrid balancer's Ts/Cs launch tables (``*_seg_*``), and the kernels
   run one *segment* per grid step: bounded work per step no matter
   how skewed the matrix, and the combine is exactly where atomic
   segments (decomposed windows/rows, shared windows) accumulate while
   non-atomic ones degenerate to stores. One block (``ts=1``) or one
   tile (``cs=ts_tile``) per segment is the per-block/per-tile launch.

5. **Heads.** Edge values may carry a head axis, ``(nnz, H)``, against
   dense operands of ``H·c`` columns that hold the heads contiguously
   (:mod:`repro.kernels.gather` states the layout). One call runs all
   heads fused: each kernel fetches an operand row once per lane tile
   (once per call where the width fits one tile) and splits its lanes
   by head. ``(nnz,)`` is the single-head case, and
   ``(nnz, 1)`` runs the same single-head kernels.

In a profile, each apply's ops fall under the named scopes ``mxu``
(padding and the MXU kernel), ``vpu`` (the VPU kernel) and ``combine``
(the epilogue), and the kernels carry their own names (``spmm_mxu``,
``spmm_vpu``, ``sddmm_mxu``, ``sddmm_vpu``; ``_mh`` appended for the
multi-head kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import WINDOW
from repro.kernels import ref
from repro.kernels.sddmm_mxu import sddmm_mxu
from repro.kernels.sddmm_vpu import sddmm_vpu
from repro.kernels.spmm_mxu import spmm_mxu
from repro.kernels.spmm_vpu import spmm_vpu
from repro.tune.model import DEFAULT_TUNE, TuneConfig, lane_tile


class ApplyError(RuntimeError):
    """Classified failure on the AOT apply path.

    ``stage`` says *where* it died — ``"compile"`` (lower/compile of a
    new executable; the cache entry is never installed, so a later
    retry re-attempts the compile) or ``"execute"`` — and ``cause`` is
    the original exception. Serving's degradation ladder keys its
    failure histograms off :func:`classify_apply_error`.
    """

    def __init__(self, stage: str, key, cause: BaseException):
        super().__init__(f"{stage} failed for apply key {key!r}: {cause}")
        self.stage = stage
        self.key = key
        self.cause = cause


def classify_apply_error(exc: BaseException) -> str:
    """Map an apply-path exception to a short failure class:
    ``compile`` | ``resource`` | ``injected`` | ``nonfinite`` |
    ``runtime``. Duck-typed (name/message heuristics for XLA's
    RESOURCE_EXHAUSTED family) so callers never import backend guts."""
    if isinstance(exc, ApplyError):
        return exc.stage if exc.stage != "execute" else \
            classify_apply_error(exc.cause)
    kind = getattr(exc, "kind", None)       # serve.faults.InjectedFault
    if kind in ("raise", "resource"):
        return "resource" if kind == "resource" else "injected"
    name = type(exc).__name__.lower()
    msg = str(exc).lower()
    if "resource" in name or "resource_exhausted" in msg \
            or "out of memory" in msg:
        return "resource"
    if "nonfinite" in name or "non-finite" in msg:
        return "nonfinite"
    return "runtime"


def cached_compile(cache: dict, key, lower, sample=None):
    """Per-operator AOT apply cache: one compiled executable per key.

    Repeated calls invoke the executable directly, skipping jit dispatch
    and re-tracing; plan arrays stay call arguments (one device copy,
    never baked into the executable as constants). ``lower`` is a thunk
    returning the lowered-but-uncompiled computation. Compile failures
    surface as :class:`ApplyError` (stage ``"compile"``) with nothing
    installed in the cache.

    ``sample`` (a ``(wall_s) -> None`` callable, usually from
    :func:`repro.obs.ledger.apply_sampler`) opts this executable into
    perf-ledger recording: each invocation is timed to completion
    (``block_until_ready``) and the wall seconds handed to ``sample``.
    """
    import time

    from repro.obs.trace import get_tracer

    tr = get_tracer()
    fn = cache.get(key)
    if fn is None:
        try:
            with tr.span("kernels.compile", key=str(key)):
                fn = cache[key] = lower().compile()
        except Exception as exc:
            raise ApplyError("compile", key, exc) from exc
    if not tr.enabled and sample is None:
        return fn

    # Instrumented path only: the executable stays raw in the cache
    # (warm()/hit accounting and explain read it directly); callers get
    # a thin wrapper that times each invocation. Ledger sampling blocks
    # on the result — async dispatch would time the enqueue, not the
    # kernel — which is why it is opt-in per call site.
    def traced(*args, **kw):
        sp = tr.span("kernels.execute", key=str(key)).open() \
            if tr.enabled else None
        try:
            if sample is not None:
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(*args, **kw))
                sample(time.perf_counter() - t0)
                return out
            return fn(*args, **kw)
        finally:
            if sp is not None:
                sp.close()

    return traced


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


#: A plan's value tensors: a revalued plan gives them a trailing head
#: axis when its edge values carry one.
_VALUE_KEYS = ("tc_seg_vals", "vpu_seg_vals")


def _plan_heads(arrs) -> int | None:
    """Heads of a plan's values: ``H`` when its VPU value tensor carries
    a trailing head axis (``(segments, width, H)``), else ``None``."""
    vals = arrs["vpu_seg_vals"]
    return vals.shape[2] if vals.ndim == 3 else None


@functools.partial(
    jax.jit,
    static_argnames=("m", "nwin", "backend", "cfg", "interpret"),
)
def spmm_apply(arrs, b, *, m: int, nwin: int, backend: str = "xla",
               cfg: TuneConfig | None = None, interpret: bool | None = None):
    """Hybrid SpMM: C[m, n] = A_sp @ B using a preprocessed Libra plan.

    ``cfg`` carries the plan's tile caps and segment caps (a
    :class:`repro.tune.model.TuneConfig`); callers that pass nothing get
    the library default — module constants no longer exist. The lane
    tile follows ``b``'s width under ``cfg.nt``
    (:func:`repro.tune.model.lane_tile`).
    ``interpret`` defaults to compiled kernels on a TPU and the Pallas
    interpreter elsewhere. A plan revalued with ``(nnz, H)`` edge values
    (:func:`repro.kernels.ref.revalue_spmm_arrays`) multiplies ``B``'s
    ``H·c`` columns head by head (head ``h``'s columns by its values).
    """
    cfg = DEFAULT_TUNE if cfg is None else cfg
    heads = _plan_heads(arrs)
    if heads == 1:
        arrs = {k: v[..., 0] if k in _VALUE_KEYS else v
                for k, v in arrs.items()}
        heads = None
    n0 = b.shape[1]
    if backend == "xla":
        return ref.spmm_hybrid_ref(arrs, b, m, nwin)
    head_dim = None
    if heads:
        assert n0 % heads == 0, (n0, heads)
        head_dim = n0 // heads
    nt = lane_tile("spmm", n0, cfg, heads=heads)
    order = "block_outer" if n0 > nt else "n_outer"
    with jax.named_scope("mxu"):
        b_p = _pad_to(b, 1, nt)
        # One grid step per segment of ≤ ts blocks of one window (§4.3
        # Ts decomposition).
        tc = spmm_mxu(arrs["tc_seg_vals"], arrs["tc_seg_cols"], b_p, nt=nt,
                      grid_order=order, head_dim=head_dim,
                      interpret=interpret)
    with jax.named_scope("vpu"):
        # §4.3 Cs decomposition: one row-segment of ≤ cs residual
        # elements per tile; the segment lengths fetch B rows for real
        # elements only.
        partials = spmm_vpu(arrs["vpu_seg_vals"], arrs["vpu_seg_cols"], b_p,
                            arrs["vpu_seg_len"], nt=nt, grid_order=order,
                            head_dim=head_dim, interpret=interpret)
    # Fused combine epilogue: the TC partials sum into their windows of
    # a zero C, then one scatter-add lays the VPU partials over it (rows
    # ≥ m from the padded last window are sliced off). This is where
    # atomic segments combine: non-atomic segments own their rows
    # exclusively (the add is a store), atomic ones — decomposed
    # windows/rows or TC∩VPU windows — deterministically accumulate in
    # segment order, the TPU analogue of the paper's
    # invoke-atomicAdd-only-when-necessary rule. Plans keep segment
    # windows and rows non-decreasing (padding repeats the last row), so
    # both sums are sorted scatters — an unsorted one makes the TPU
    # compiler sort the updates, tens of seconds of compile at graph
    # scale.
    with jax.named_scope("combine"):
        tc_win = arrs["tc_seg_row"][::WINDOW] // WINDOW
        out = jax.ops.segment_sum(
            tc.reshape(-1, WINDOW, tc.shape[-1]), tc_win, num_segments=nwin,
            indices_are_sorted=True).reshape(nwin * WINDOW, -1)
        out = out.at[arrs["vpu_seg_row"]].add(partials,
                                              indices_are_sorted=True)
        return out[:m, :n0]


def map_batch(backend: str, fn, *xs):
    """``fn`` over the leading axis of ``xs``: ``vmap`` for the XLA
    reference, a sequential ``lax.map`` for the Pallas kernels, whose
    HBM-resident operands (``memory_space=pl.ANY``) cannot take the grid
    axis ``vmap`` would add. Each element runs the single-call program
    either way."""
    if backend == "xla":
        return jax.vmap(fn)(*xs)
    return jax.lax.map(lambda a: fn(*a), xs)


def spmm_apply_stack(arrs, b_stack, *, m: int, nwin: int,
                     backend: str = "xla", cfg: TuneConfig | None = None,
                     edge_vals: jnp.ndarray | None = None) -> jnp.ndarray:
    """Panel-stack hybrid SpMM: one plan over a ``(batch, k, n)`` stack.

    The serving-shape primitive: a graph's plan is the amortized asset,
    requests arrive as feature panels. Mapping the single fused apply
    over the stack (:func:`map_batch`) keeps per-panel results bitwise
    identical to looped single applies (each batch element's compute
    graph is the single-panel one), so bucketed serving can promise
    bit-identity with direct operator calls. ``edge_vals`` — optional
    ``(batch, nnz)`` canonical per-panel values — revalues the plan per
    panel inside the map (the attention-serving path: pattern shared,
    values per request).

    Traceable; callers AOT-compile via :func:`cached_compile` (see
    :class:`repro.dist.sparse.BatchedSpMM` / the serve engine).
    """
    one = functools.partial(spmm_apply, m=m, nwin=nwin, backend=backend,
                            cfg=cfg)
    if edge_vals is None:
        return map_batch(backend, lambda bb: one(arrs, bb), b_stack)
    return map_batch(
        backend, lambda ev, bb: one(ref.revalue_spmm_arrays(arrs, ev), bb),
        edge_vals, b_stack)


def sddmm_apply_stack(arrs, x_stack, y_stack, *, nnz: int,
                      backend: str = "xla", cfg: TuneConfig | None = None
                      ) -> jnp.ndarray:
    """Panel-stack hybrid SDDMM: ``(batch, m, kf) × (batch, k, kf) →
    (batch, nnz)`` — see :func:`spmm_apply_stack` for the contract."""
    one = functools.partial(sddmm_apply, nnz=nnz, backend=backend, cfg=cfg)
    return map_batch(backend, lambda xx, yy: one(arrs, xx, yy), x_stack,
                     y_stack)


@functools.partial(
    jax.jit, static_argnames=("nnz", "backend", "cfg", "heads", "interpret")
)
def sddmm_apply(arrs, x, y, *, nnz: int, backend: str = "xla",
                cfg: TuneConfig | None = None, heads: int | None = None,
                interpret: bool | None = None):
    """Hybrid SDDMM: values[nnz] = sample(X @ Yᵀ) in canonical CSR order.

    The feature dimension is tiled under ``cfg.kf_tile`` as its width
    allows (:func:`repro.tune.model.lane_tile`), padded here to whole
    tiles — a lane-dense row is 128 wide in HBM regardless; padded
    features are zeros. With ``heads`` = H, X and Y hold H heads of
    ``kf / H`` columns each and the values are ``(nnz, H)``, one score
    per head.
    """
    cfg = DEFAULT_TUNE if cfg is None else cfg
    if heads == 1:
        return sddmm_apply(arrs, x, y, nnz=nnz, backend=backend, cfg=cfg,
                           interpret=interpret)[:, None]
    if backend == "xla":
        return ref.sddmm_hybrid_ref(arrs, _pad_to(x, 0, WINDOW), y, nnz,
                                    heads)
    head_dim = None
    if heads:
        assert x.shape[1] % heads == 0, (x.shape, heads)
        head_dim = x.shape[1] // heads
    kft = lane_tile("sddmm", x.shape[1], cfg, heads=heads)
    with jax.named_scope("mxu"):
        x = _pad_to(x, 1, kft)
        y = _pad_to(y, 1, kft)
        x_p = _pad_to(x, 0, WINDOW)
        # §4.3 Ts decomposition: one grid step scores a whole segment of
        # ≤ ts blocks sharing a window — one 8×kf @ kf×(ts·bk) dot,
        # bitmap-sampled (zero bitmap padding samples to zero and its
        # out_pos −1 lands in the scatter's swallow slot).
        s_tc = sddmm_mxu(arrs["tc_seg_cols"], arrs["tc_seg_bitmap"],
                         arrs["tc_seg_window"], x_p, y, kf_tile=kft,
                         heads=heads, head_dim=head_dim, interpret=interpret)
    with jax.named_scope("vpu"):
        # The Cs cap batches whole element tiles per VPU grid step.
        vpu_mask = arrs["vpu_seg_mask"]
        s_el = sddmm_vpu(arrs["vpu_seg_rows"], arrs["vpu_seg_cols"], x, y,
                         kf_tile=kft, heads=heads, head_dim=head_dim,
                         interpret=interpret)
    # Fused combine: one scatter of both streams into the canonical nnz
    # vector (slot nnz swallows -1/masked padding).
    with jax.named_scope("combine"):
        if not heads:
            s_el = jnp.where(vpu_mask, s_el, 0.0)
        tc_pos = arrs["tc_seg_out_pos"]
        pos_tc = jnp.where(tc_pos >= 0, tc_pos, nnz)
        pos_el = jnp.where(vpu_mask, arrs["vpu_seg_out_pos"], nnz)
        pos = jnp.concatenate([pos_tc.reshape(-1), pos_el.reshape(-1)])
        if heads:
            # Each non-zero's scores sit at exactly one output position,
            # so each is gathered from there, by the position map's
            # inverse (a function of the plan alone, shared by every call
            # of a step). On a v5e a scatter of rows of H scores cost
            # ~11× the single-head scatter per call.
            src = jnp.zeros((nnz + 1,), jnp.int32).at[pos].set(
                jnp.arange(pos.shape[0], dtype=jnp.int32))[:nnz]
            data = jnp.concatenate(
                [jnp.moveaxis(s_tc, 1, 0).reshape(heads, -1),
                 s_el.reshape(heads, -1)], axis=1)     # (H, positions)
            return jnp.take(data, src, axis=1).T
        data = jnp.concatenate([s_tc.reshape(-1), s_el.reshape(-1)])
        out = jnp.zeros((nnz + 1,), s_tc.dtype).at[pos].add(data)
        return out[:nnz]
