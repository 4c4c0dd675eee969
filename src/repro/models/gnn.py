"""GNN models (GCN, AGNN, UniMP, Exphormer) on Libra hybrid sparse
operators.

This is the paper's end-to-end application (§5.5): SpMM performs feature
aggregation, SDDMM computes per-edge attention. Gradients follow the
classic duality — the VJP of a value-parameterized SpMM is an SpMM with
the transposed plan (for features) plus an SDDMM with the same sparsity
(for edge values) — so *every* matmul in training runs through Libra ops.

Edge values are ``(nnz,)``, or ``(nnz, H)`` for multi-head attention
(UniMP): the dense operands then hold H heads of ``c`` features
contiguously, and every sparse call runs all heads fused
(:mod:`repro.kernels.ops`). Exphormer attends over several edge types,
each sparse type through a :class:`GraphOps` of its own, with one
softmax across them (:func:`edge_softmax` with ``more``).
"""
from __future__ import annotations

import contextlib
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
from repro.obs.trace import NULL_SPAN, get_tracer
from repro.tune.model import DEFAULT_TUNE, lane_tile
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

    def sddmm(self, x, y, heads: int | None = None):
        """vals[p] = ⟨X[row_p], Y[col_p]⟩, differentiable in (x, y); with
        ``heads`` = H, one score per head, ``(nnz, H)``."""
        return _sddmm_ev(self, x, y, heads)

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


@contextlib.contextmanager
def call_spans(op: str, heads: int | None, width: int, cfg):
    """The spans of one sparse call site, on an enabled tracer only: a
    ``graphops.tile`` span with the lane tile the call takes under
    ``cfg`` (:func:`repro.tune.model.lane_tile`) as attributes
    (:func:`repro.obs.explain.tile_counts`), inside a ``graphops.heads``
    span with its head layout (:func:`repro.obs.explain.head_counts`)
    for a multi-head call. Calls are traced once per compile, so the
    spans count call sites of a traced step."""
    tr = get_tracer()
    if not tr.enabled:
        yield
        return
    from repro.obs.explain import head_counts, tile_counts

    tile = lane_tile(op, width, cfg or DEFAULT_TUNE, heads=heads)
    heads_span = NULL_SPAN if not heads or heads == 1 else tr.span(
        "graphops.heads", op=op, **head_counts(heads, width // heads, tile))
    with heads_span, tr.span("graphops.tile", op=op,
                             **tile_counts(width, tile)):
        yield


def edge_heads(edge_vals) -> int | None:
    """``H`` for ``(nnz, H)`` edge values, ``None`` for ``(nnz,)``."""
    return edge_vals.shape[1] if edge_vals.ndim == 2 else None


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
    with call_spans("spmm", edge_heads(edge_vals), b.shape[1], cfg), \
            jax.named_scope("spmm"):
        with jax.named_scope("revalue"):
            if transposed:
                edge_vals = edge_vals[g.perm_dev]
            arrs = ref.revalue_spmm_arrays(arrs, edge_vals)
        out = spmm_apply(arrs, b, m=m, nwin=nwin, backend=g.backend,
                         cfg=cfg)
        with jax.named_scope("combine"):
            return _unreorder(out, unperm)


def _sddmm(g: GraphOps, x, y, heads: int | None = None):
    """``vals[p] = ⟨x[row_p], y[col_p]⟩`` (per head with ``heads``) under
    the ``sddmm`` scope."""
    with call_spans("sddmm", heads, x.shape[1], g.cfg_sd), \
            jax.named_scope("sddmm"):
        return sddmm_apply(g.arrs_sd, _reorder_x(x, g._x_perm), y,
                           nnz=g.nnz, backend=g.backend, cfg=g.cfg_sd,
                           heads=heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_ev(g: GraphOps, edge_vals, b):
    return _spmm(g, edge_vals, b)


def _spmm_ev_fwd(g, edge_vals, b):
    return _spmm_ev(g, edge_vals, b), (edge_vals, b)


def _spmm_ev_bwd(g, resid, d_c):
    edge_vals, b = resid
    # dB = A(v)^T @ dC — SpMM on the transposed plan with permuted values.
    d_b = _spmm(g, edge_vals, d_c, transposed=True)
    # dv[p] = dC[row_p] · B[col_p] — SDDMM with A's sparsity (per head).
    d_vals = _sddmm(g, d_c, b, edge_heads(edge_vals))
    return d_vals.astype(edge_vals.dtype), d_b.astype(b.dtype)


_spmm_ev.defvjp(_spmm_ev_fwd, _spmm_ev_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _sddmm_ev(g: GraphOps, x, y, heads):
    return _sddmm(g, x, y, heads)


def _sddmm_ev_fwd(g, x, y, heads):
    return _sddmm_ev(g, x, y, heads), (x, y)


def _sddmm_ev_bwd(g, heads, resid, d_vals):
    x, y = resid
    # dX = A(dv) @ Y ; dY = A(dv)^T @ X — both SpMMs through Libra plans.
    d_x = _spmm(g, d_vals, y)
    d_y = _spmm(g, d_vals, x, transposed=True)
    return d_x.astype(x.dtype), d_y.astype(y.dtype)


_sddmm_ev.defvjp(_sddmm_ev_fwd, _sddmm_ev_bwd)


def edge_softmax(g: GraphOps, scores, *, more=(), dense=None):
    """Numerically stable per-destination-row softmax over edge scores,
    ``(nnz,)`` or ``(nnz, H)`` (each head apart).

    With ``more`` further ``(ops, scores)`` parts over the same rows
    (each its own :class:`GraphOps`, so a pair may be an edge of several
    parts and counts once in each) and ``dense``, one more score per row
    (``(m,)`` or ``(m, H)``), the softmax of a row spans all its edges of
    every part and its dense score together: one row max and one row sum
    across them, under the ``edge_softmax/typed_softmax`` scope. Returns
    the weights of each part in order, then the dense term's; the
    one-part call returns its weights alone, as it always has."""
    if not more and dense is None:
        with jax.named_scope("edge_softmax"):
            mx = jax.ops.segment_max(scores, g.edge_row, num_segments=g.m)
            e = jnp.exp(scores - mx[g.edge_row])
            z = jax.ops.segment_sum(e, g.edge_row, num_segments=g.m)
            return e / jnp.maximum(z[g.edge_row], 1e-9)
    parts = [(g, scores), *more]
    if any(ops.m != g.m for ops, _ in parts):
        raise ValueError("edge_softmax: parts over different row counts "
                         f"{[ops.m for ops, _ in parts]}")
    with jax.named_scope("edge_softmax"), jax.named_scope("typed_softmax"):
        # The rows' max only steadies the exponent; its gradient cancels.
        tops = [jax.ops.segment_max(s, ops.edge_row, num_segments=g.m,
                                    indices_are_sorted=True)
                for ops, s in parts]
        if dense is not None:
            tops.append(dense)
        mx = jax.lax.stop_gradient(functools.reduce(jnp.maximum, tops))
        es = [jnp.exp(s - mx[ops.edge_row]) for ops, s in parts]
        sums = [jax.ops.segment_sum(e, ops.edge_row, num_segments=g.m,
                                    indices_are_sorted=True)
                for e, (ops, _) in zip(es, parts)]
        if dense is not None:
            es.append(jnp.exp(dense - mx))
            sums.append(es[-1])
        z = jnp.maximum(functools.reduce(jnp.add, sums), 1e-9)
        out = [e / z[ops.edge_row] for e, (ops, _) in zip(es, parts)]
        if dense is not None:
            out.append(es[-1] / z)
        return tuple(out)


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


# ---------------------------------------------------------------- UniMP ---
def init_unimp(rng, dims: list[int], heads: int):
    """UniMP layers (Shi et al., arXiv:2009.03509; PyG's
    ``TransformerConv`` with ``beta=True``), widths ``dims``. Every layer
    but the last concatenates ``heads`` heads of ``dims[i+1] // heads``
    features; the last averages ``heads`` heads of ``dims[-1]``. Query,
    key, value and skip projections carry biases, the gate none, as PyG's
    defaults; weights are Glorot-scaled normal, biases uniform in
    ``±1/√d_in`` (PyG ``Linear``'s bias init); each layer but the last
    has a LayerNorm (scale 1, shift 0) after it.

    Weights are ``(d_in, H, c)`` and biases ``(H, c)`` for the query,
    key and value, so a layer's parameters give its head layout."""
    layers = []
    for i, key in enumerate(jax.random.split(rng, len(dims) - 1)):
        d_in, d_out = dims[i], dims[i + 1]
        last = i == len(dims) - 2
        c = d_out if last else d_out // heads
        ks = jax.random.split(key, 9)
        bound = 1.0 / np.sqrt(d_in)

        def weight(k, shape, fan):
            return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan)

        def bias(k, shape):
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

        lp = {"q_w": weight(ks[0], (d_in, heads, c), d_in),
              "q_b": bias(ks[1], (heads, c)),
              "k_w": weight(ks[2], (d_in, heads, c), d_in),
              "k_b": bias(ks[3], (heads, c)),
              "v_w": weight(ks[4], (d_in, heads, c), d_in),
              "v_b": bias(ks[5], (heads, c)),
              "r_w": weight(ks[6], (d_in, d_out), d_in),
              "r_b": bias(ks[7], (d_out,)),
              "beta_w": weight(ks[8], (3 * d_out,), 3 * d_out)}
        if not last:
            lp["ln_g"] = jnp.ones((d_out,), jnp.float32)
            lp["ln_b"] = jnp.zeros((d_out,), jnp.float32)
        layers.append(lp)
    return layers


def _unimp_layer(lp, g, x, concat: bool):
    """One ``TransformerConv`` (``beta=True``): per head ``h``,
    ``α_ij = softmax_j(q_i·k_j / √c)`` over row ``i``'s edges (SDDMM →
    edge softmax), ``m_i = Σ_j α_ij v_j`` (SpMM), all heads in one fused
    call each; heads concatenated or averaged; then the gate
    ``β = σ(w_βᵀ[r ‖ m ‖ r − m])`` mixes the skip path ``r`` with ``m``."""
    d_in, heads, c = lp["q_w"].shape
    with jax.named_scope("qkv"):
        def proj(name):
            return x @ lp[f"{name}_w"].reshape(d_in, -1) \
                + lp[f"{name}_b"].reshape(-1)
        # 1/√c rides on q, so the scores leave the SDDMM scaled.
        q = proj("q") * (1.0 / np.sqrt(c))
        k, v = proj("k"), proj("v")
        r = x @ lp["r_w"] + lp["r_b"]
    att = edge_softmax(g, g.sddmm(q, k, heads=heads))
    m = g.spmm(att, v)
    if not concat:
        m = m.reshape(-1, heads, c).mean(axis=1)
    with jax.named_scope("gate"):
        beta = jax.nn.sigmoid(
            jnp.concatenate([r, m, r - m], axis=-1) @ lp["beta_w"])[:, None]
        return beta * r + (1.0 - beta) * m


def _layer_norm(h, scale, shift, eps: float = 1e-5):
    mu = h.mean(axis=-1, keepdims=True)
    var = ((h - mu) ** 2).mean(axis=-1, keepdims=True)
    return (h - mu) * jax.lax.rsqrt(var + eps) * scale + shift


def unimp_forward(params, g: GraphOps, x):
    """UniMP: ``TransformerConv`` layers (:func:`init_unimp`), each but
    the last followed by LayerNorm and ReLU."""
    h = x
    for i, lp in enumerate(params):
        last = i == len(params) - 1
        h = _unimp_layer(lp, g, h, concat=not last)
        if not last:
            h = jax.nn.relu(_layer_norm(h, lp["ln_g"], lp["ln_b"]))
    return h


# ------------------------------------------------------------ Exphormer ---
#: Exphormer's score clamp (the authors' ``clamp(-5, 5)`` before ``exp``).
SCORE_CLAMP = 5.0

#: Exphormer's edge types, each with a key scaling of its own.
EDGE_TYPES = ("graph", "expander", "virtual")


def expander_csr(n: int, perms: int, seed: int) -> SparseCSR:
    """Exphormer's expander over ``n`` nodes (Shirzad et al.,
    arXiv:2303.06147): for each of ``perms`` random permutations ``π``
    drawn from ``seed``, the pairs ``(i, π(i))`` and ``(π(i), i)``; self
    pairs and repeats dropped. Symmetric, at most ``2·perms`` non-zeros a
    row, values 1, the same for the same arguments. Under an enabled
    tracer one ``exphormer.expander`` span with counters ``nodes``,
    ``perms``, ``nnz`` and ``dropped`` (pairs drawn less ``nnz``)."""
    with get_tracer().span("exphormer.expander", nodes=n,
                           perms=perms) as sp:
        rng = np.random.default_rng(seed)
        src = np.tile(np.arange(n, dtype=np.int64), perms)
        dst = np.concatenate([rng.permutation(n) for _ in range(perms)])
        rows = np.concatenate([src, dst])
        cols = np.concatenate([dst, src])
        keep = rows != cols
        key = np.unique(rows[keep] * n + cols[keep])
        sp.set(nnz=int(key.size), dropped=int(rows.size - key.size))
        return coo_to_csr(n, n, key // n, key % n,
                          np.ones(key.size, np.float32))


def init_exphormer(rng, dims: list[int], heads: int):
    """Exphormer with GraphGPS blocks: an encoder ``dims[0] → w``
    (``w = dims[1]``), ``len(dims) - 1`` GPS blocks of width ``w`` in
    ``heads`` heads of ``w // heads``, and a head ``w → dims[-1]``;
    the virtual node's starting state ``virtual``. Per block: the GCN
    local's ``l_w``, ``l_b``; query, key and value weights ``(w, H, c)``
    and biases ``(H, c)``; one key scaling ``(H, c)`` per edge type
    (``e_graph``, ``e_expander``, ``e_virtual``); the attention output's
    ``o_w``, ``o_b``; the FFN's ``f1`` (``w → 2w``) and ``f2``; three
    BatchNorms' scales and shifts. Weights Glorot-scaled normal, biases
    uniform in ``±1/√fan_in``, type scalings and ``virtual`` standard
    normal, BatchNorm 1 and 0."""
    d_in, w, n_out = dims[0], dims[1], dims[-1]
    if any(d != w for d in dims[1:-1]):
        raise ValueError(f"init_exphormer: one hidden width, got {dims}")
    c = w // heads
    keys = iter(jax.random.split(rng, 5 + 17 * (len(dims) - 1)))

    def weight(shape, fan):
        return jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan)

    def bias(shape, fan):
        b = 1.0 / np.sqrt(fan)
        return jax.random.uniform(next(keys), shape, jnp.float32, -b, b)

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    params = {"in_w": weight((d_in, w), d_in), "in_b": bias((w,), d_in),
              "virtual": normal((w,)), "layers": []}
    for _ in range(len(dims) - 1):
        lp = {"l_w": weight((w, w), w), "l_b": bias((w,), w)}
        for name in ("q", "k", "v"):
            lp[f"{name}_w"] = weight((w, heads, c), w)
            lp[f"{name}_b"] = bias((heads, c), w)
        for t in EDGE_TYPES:
            lp[f"e_{t}"] = normal((heads, c))
        lp.update(o_w=weight((w, w), w), o_b=bias((w,), w),
                  f1_w=weight((w, 2 * w), w), f1_b=bias((2 * w,), w),
                  f2_w=weight((2 * w, w), 2 * w), f2_b=bias((w,), 2 * w))
        for i in (1, 2, 3):
            lp[f"bn{i}_g"] = jnp.ones((w,), jnp.float32)
            lp[f"bn{i}_b"] = jnp.zeros((w,), jnp.float32)
        params["layers"].append(lp)
    params.update(out_w=weight((w, n_out), w), out_b=bias((n_out,), w))
    return params


def _batch_norm(h, scale, shift, eps: float = 1e-5):
    """BatchNorm in training mode: each feature by its batch statistics
    over the rows."""
    mu = h.mean(axis=0)
    var = ((h - mu) ** 2).mean(axis=0)
    return (h - mu) * jax.lax.rsqrt(var + eps) * scale + shift


def _clamp(s):
    return jnp.clip(s, -SCORE_CLAMP, SCORE_CLAMP)


def _exphormer_attention(lp, g: GraphOps, gx: GraphOps, h):
    """One attention over the interaction graph of ``h``'s ``n`` real
    rows and its virtual row ``n``: per head, ``s_ij = clamp((q_i ⊙
    e_t)·k_j / √c, ±5)`` on each edge of type ``t``, one softmax over all
    typed edges of a row, ``m_i = Σ α_ij v_j``. Graph edges (``g``'s
    plan) and expander edges (``gx``'s) run the fused multi-head SDDMM
    and SpMM; the virtual node's edges (each real row to it, it to every
    real row) and its own graph self loop are dense."""
    n = g.m
    w, heads, c = lp["q_w"].shape
    with jax.named_scope("qkv"):
        def proj(name):
            return h @ lp[f"{name}_w"].reshape(w, -1) \
                + lp[f"{name}_b"].reshape(-1)
        # 1/√c rides on q, so the scores leave the SDDMMs scaled.
        q = proj("q") * (1.0 / np.sqrt(c))
        k, v = proj("k"), proj("v")
    q_r, k_r, v_r = q[:n], k[:n], v[:n]
    q_n, k_n, v_n = (t[n].reshape(heads, c) for t in (q, k, v))
    e = {t: lp[f"e_{t}"] for t in EDGE_TYPES}
    tr = get_tracer()
    types = NULL_SPAN
    if tr.enabled:
        from repro.obs.explain import type_counts

        types = tr.span("graphops.types", **type_counts(
            {"graph": g.nnz, "expander": gx.nnz}, 2 * n + 1))
    with types:
        with jax.named_scope("attn"), jax.named_scope("graph"):
            s_g = _clamp(g.sddmm(q_r * e["graph"].reshape(-1), k_r,
                                 heads=heads))
        with jax.named_scope("attn"), jax.named_scope("expander"):
            s_x = _clamp(gx.sddmm(q_r * e["expander"].reshape(-1), k_r,
                                  heads=heads))
        with jax.named_scope("attn"), jax.named_scope("virtual"):
            k_r3 = k_r.reshape(n, heads, c)
            s_v = _clamp(jnp.einsum("nhc,hc->nh",
                                    q_r.reshape(n, heads, c) * e["virtual"],
                                    k_n))
        a_g, a_x, a_v = edge_softmax(g, s_g, more=[(gx, s_x)], dense=s_v)
        with jax.named_scope("attn"), jax.named_scope("graph"):
            m = g.spmm(a_g, v_r)
        with jax.named_scope("attn"), jax.named_scope("expander"):
            m = m + gx.spmm(a_x, v_r)
        with jax.named_scope("attn"), jax.named_scope("virtual"):
            m = m + (a_v[:, :, None] * v_n).reshape(n, w)
            # The virtual row: its edge to every real row and its graph
            # self loop, in one softmax over n + 1 scores a head.
            s_out = _clamp(jnp.einsum("hc,nhc->nh", q_n * e["virtual"], k_r3))
            s_self = _clamp(jnp.sum(q_n * e["graph"] * k_n, axis=-1))
            a_n = jax.nn.softmax(jnp.concatenate([s_out, s_self[None]]),
                                 axis=0)
            m_n = (jnp.einsum("nh,nhc->hc", a_n[:n],
                              v_r.reshape(n, heads, c))
                   + a_n[n][:, None] * v_n)
        return jnp.concatenate([m, m_n.reshape(1, w)])


def _gps_layer(lp, g: GraphOps, gx: GraphOps, h, norm_vals):
    """One GPS block: ``l = BN₁(h + Â h W_l + b_l)`` (the virtual row's
    only graph edge its self loop, ``Â_nn = 1``), ``a = BN₂(h + m W_o +
    b_o)`` with ``m`` the typed attention, ``y = l + a``, ``h' = BN₃(y +
    W₂ ReLU(W₁ y + b₁) + b₂)``."""
    n = g.m
    with jax.named_scope("gps"), jax.named_scope("local"):
        z = h @ lp["l_w"]
        agg = jnp.concatenate([g.spmm(norm_vals, z[:n]), z[n:]])
        local = _batch_norm(h + agg + lp["l_b"], lp["bn1_g"], lp["bn1_b"])
    m = _exphormer_attention(lp, g, gx, h)
    att = _batch_norm(h + m @ lp["o_w"] + lp["o_b"], lp["bn2_g"], lp["bn2_b"])
    with jax.named_scope("gps"), jax.named_scope("ffn"):
        y = local + att
        f = jax.nn.relu(y @ lp["f1_w"] + lp["f1_b"]) @ lp["f2_w"] + lp["f2_b"]
        return _batch_norm(y + f, lp["bn3_g"], lp["bn3_b"])


def exphormer_forward(params, g: GraphOps, gx: GraphOps, x, norm_vals):
    """Exphormer (:func:`init_exphormer`) on ``x``'s ``n`` real nodes and
    one virtual node: encoder, GPS blocks over ``g`` (the graph with self
    loops, the GCN local's ``Â`` values ``norm_vals`` in its order) and
    ``gx`` (the expander, :func:`expander_csr`), then the head on the
    real rows: the logits, ``(n, classes)``."""
    h = jnp.concatenate([x @ params["in_w"] + params["in_b"],
                         params["virtual"][None]])
    for lp in params["layers"]:
        h = _gps_layer(lp, g, gx, h, norm_vals)
    return h[:g.m] @ params["out_w"] + params["out_b"]
