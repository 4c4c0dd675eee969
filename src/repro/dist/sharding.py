"""2D (data × model) GSPMD sharding rules shared by train/serve/dry-run.

One place decides where every tensor lives:

* **Logical axes.** Layer code never names mesh axes directly; it asks for
  ``"batch"`` (all data axes of the current mesh) or ``"model"`` (the
  tensor-parallel axis) through :func:`constrain`. Meshes may be 2D
  (``data × model``) or 3D (``pod × data × model``) — ``"batch"`` expands
  to every non-model axis, so the same layer code runs on both.
* **Divisibility sanitation.** GSPMD requires sharded dims to divide the
  axis product; :func:`sanitize_spec` drops (replicates) any entry that
  does not divide, so odd vocab/head counts degrade gracefully instead of
  erroring.
* **Context, not globals-by-import.** :func:`activation_context` installs
  the mesh (and the small-model ``dp_only`` escape hatch) for the scope of
  one traced step; outside any context every helper is a no-op, which is
  what keeps the single-device unit tests oblivious to all of this.

Parameter placement (:func:`spec_for`) follows the standard Megatron-style
2D layout: weight matrices shard their penultimate dim over ``data`` (ZeRO
/ FSDP-ish) and their last dim over ``model``; embeddings transpose that
(``vocab`` over ``model`` so the unembed matmul is TP-local).
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"

_ctx = threading.local()


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    These rules place tensors with GSPMD sharding constraints, which
    only ``Auto`` axes accept; ``jax.make_mesh`` builds ``Explicit``
    ones by default.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# ----------------------------------------------------------- mesh axes ---
def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Every mesh axis that is not the tensor-parallel axis."""
    return tuple(n for n in mesh.axis_names if n != MODEL_AXIS)


def _axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def _entry_size(mesh: Mesh, entry) -> int:
    """Total number of shards one PartitionSpec entry implies."""
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= _axis_size(mesh, n)
    return size


def _entry_valid(mesh: Mesh, entry) -> bool:
    names = entry if isinstance(entry, tuple) else (entry,)
    return all(n in mesh.axis_names for n in names)


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Replicate every spec entry whose axis product does not divide the
    corresponding dim (or that names axes absent from the mesh)."""
    out = []
    for d, entry in enumerate(tuple(spec)):
        if entry is None or d >= len(shape):
            out.append(None)
            continue
        if not _entry_valid(mesh, entry):
            out.append(None)
            continue
        size = _entry_size(mesh, entry)
        out.append(entry if size and shape[d] % size == 0 else None)
    return P(*out)


# ------------------------------------------------------- step context ----
def dp_only_of(cfg) -> bool:
    """Small-model escape hatch: batch over *all* mesh axes, no TP."""
    return bool(getattr(cfg, "dp_only", False))


@contextlib.contextmanager
def activation_context(mesh: Mesh, dp_only: bool = False):
    """Install the mesh for :func:`constrain` & friends during tracing."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, bool(dp_only))
    try:
        yield
    finally:
        _ctx.state = prev


def _current():
    return getattr(_ctx, "state", None)


def current_mesh_info():
    """(mesh, batch-axes spec entry) of the active context, or (None, None).

    The second element is what ``"batch"`` resolves to — a tuple of axis
    names usable directly as one PartitionSpec entry.
    """
    state = _current()
    if state is None:
        return None, None
    mesh, dp_only = state
    ba = tuple(mesh.axis_names) if dp_only else data_axes(mesh)
    return mesh, ba


def model_axis_size() -> int:
    """Size of the TP axis in the active context (1 outside / dp_only)."""
    state = _current()
    if state is None:
        return 1
    mesh, dp_only = state
    return 1 if dp_only else _axis_size(mesh, MODEL_AXIS)


def batch_shard_count() -> int:
    """Number of batch shards in the active context (1 outside)."""
    mesh, ba = current_mesh_info()
    if mesh is None:
        return 1
    size = 1
    for n in ba:
        size *= _axis_size(mesh, n)
    return size


def kv_repeat_for_tp(kv: int, h: int) -> int:
    """How many times to repeat KV heads so the kv-head dim divides the TP
    axis (GQA groups absorb the repetition). 1 outside a context, when the
    split already divides, or when no valid repetition exists."""
    mt = model_axis_size()
    if mt <= 1 or kv % mt == 0:
        return 1
    rep = mt // math.gcd(kv, mt)
    if rep > 1 and kv * rep <= h and h % (kv * rep) == 0:
        return rep
    return 1


def constrain(x, *axes):
    """``with_sharding_constraint`` by logical axis names.

    Each positional entry names the placement of one dim of ``x``:
    ``"batch"`` (data axes), ``"model"`` (TP axis) or None (replicated).
    No-op outside an :func:`activation_context`; under ``dp_only`` the
    model axis is ignored and batch spans the whole mesh.
    """
    state = _current()
    if state is None:
        return x
    mesh, dp_only = state
    _, ba = current_mesh_info()
    entries = []
    for a in axes:
        if a == "batch":
            entries.append(ba if ba else None)
        elif a == MODEL_AXIS:
            entries.append(None if dp_only else MODEL_AXIS)
        else:
            entries.append(a)
    spec = sanitize_spec(P(*entries), tuple(x.shape), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------- placement rules -----
def _key_names(path) -> list[str]:
    out = []
    for part in path:
        key = getattr(part, "key", None)
        if key is None:
            key = getattr(part, "name", part)
        out.append(str(key))
    return out


def spec_for(path, leaf) -> P:
    """Logical parameter spec from a pytree key path + leaf aval.

    Rules (resolved against a concrete mesh by :func:`param_shardings`):
    embeddings → ``P("model", "data")`` (vocab over TP so unembed stays
    local); everything else with ≥2 dims → last-two-dims ``("data",
    "model")`` with leading stacked/layer dims replicated; vectors and
    scalars → replicated.
    """
    ndim = getattr(leaf, "ndim", 0)
    names = _key_names(path)
    if any("embed" in n for n in names) and ndim >= 2:
        return P(*([None] * (ndim - 2) + [MODEL_AXIS, "data"]))
    if ndim >= 2:
        return P(*([None] * (ndim - 2) + ["data", MODEL_AXIS]))
    return P(*([None] * ndim))


def _resolve(mesh: Mesh, spec: P) -> P:
    """Map the logical ``"data"`` entry onto every data axis of the mesh
    (so a 3D ``pod×data×model`` mesh shards over pod+data together)."""
    da = data_axes(mesh)
    out = []
    for entry in tuple(spec):
        if entry == "data":
            out.append(da if len(da) > 1 else (da[0] if da else None))
        else:
            out.append(entry)
    return P(*out)


def _leaf_sharding(mesh: Mesh, spec: P, leaf) -> NamedSharding:
    shape = tuple(getattr(leaf, "shape", ()))
    return NamedSharding(mesh, sanitize_spec(_resolve(mesh, spec), shape, mesh))


def param_shardings(mesh: Mesh, tree, replicate: bool = False):
    """NamedSharding pytree for a parameter pytree (or its avals)."""
    def one(path, leaf):
        if replicate:
            return NamedSharding(mesh, P())
        return _leaf_sharding(mesh, spec_for(path, leaf), leaf)

    return jax.tree_util.tree_map_with_path(one, tree)


def batch_spec(mesh: Mesh, ndim: int) -> P:
    """Batch tensors shard dim 0 over the data axes, rest replicated."""
    da = data_axes(mesh)
    first = da if len(da) > 1 else (da[0] if da else None)
    return P(*([first] + [None] * (ndim - 1)))


def batch_shardings(mesh: Mesh, tree):
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh,
            sanitize_spec(batch_spec(mesh, getattr(leaf, "ndim", 0)),
                          tuple(getattr(leaf, "shape", ())), mesh),
        ),
        tree,
    )


def cache_shardings(mesh: Mesh, cache):
    """Decode caches: dim 0 (batch) over data, the head/state dim (−2 for
    rank ≥ 3) over model — matches the attention layout (B, S, KV, D)."""
    def one(leaf):
        ndim = getattr(leaf, "ndim", 0)
        entries = [None] * ndim
        if ndim >= 1:
            da = data_axes(mesh)
            entries[0] = da if len(da) > 1 else (da[0] if da else None)
        if ndim >= 3:
            entries[-2] = MODEL_AXIS
        return NamedSharding(
            mesh,
            sanitize_spec(P(*entries), tuple(getattr(leaf, "shape", ())),
                          mesh),
        )

    return jax.tree.map(one, cache)
