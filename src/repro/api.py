"""`ExecSpec`: the one execution-knob surface for every Libra operator.

Before this module, the same knobs — ``tune=``, ``tune_backend=``,
``tune_cache=``, ``backend=``, ``mode=``, per-op
thresholds, and now ``reorder=`` — were duplicated (with drifting
defaults) across :class:`~repro.core.spmm.LibraSpMM`,
:class:`~repro.core.sddmm.LibraSDDMM`, ``GraphOps``, ``DistGraphOps``,
the partitioners, ``ShardedSpMM``/``ShardedSDDMM`` and
``GraphRegistry.register``, with ``dist/sparse.py`` forwarding untyped
``**op_kwargs`` bags between tiers. Every one of those call sites now
accepts ``spec=ExecSpec(...)`` and resolves knobs in one order:

    **explicit kwarg > spec field > default.**

Legacy kwargs keep working through :func:`resolve_spec` — a shim that
folds them into a spec and emits one :class:`DeprecationWarning` per
call site (not per call).

Example::

    from repro.api import ExecSpec

    spec = ExecSpec(mode="tcu", tune="search", reorder="auto")
    op = LibraSpMM(a, spec=spec)          # canonical form
    op = LibraSpMM(a, mode="tcu")         # legacy shim: works, warns once

``ExecSpec`` is frozen and hashable, so it can key plan caches and be
shared across operators, shards and registry entries.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

from repro.tune.model import TuneConfig

#: Sentinel distinguishing "caller did not pass this kwarg" from an
#: explicit ``None`` (many knobs use None as a meaningful default).
UNSET: Any = type("_Unset", (), {"__repr__": lambda s: "UNSET",
                                 "__bool__": lambda s: False})()

_REORDER_MODES = ("auto", "on", "off")


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Frozen, hashable execution spec accepted by every operator tier.

    Plan shape:
      mode:             "hybrid" | "tcu" | "vpu" (paper §5.4.1 ablations)
      threshold:        SpMM TC/VPU vector threshold (None → tuner/default)
      sddmm_threshold:  SDDMM block threshold (None → tuner/default)
      bk / ts_tile:     condensed block depth / VPU tile width overrides
      reorder:          "auto" | "on" | "off" — sparsity-aware row
                        reordering (:mod:`repro.reorder`); "auto" prices
                        the permutation from the matrix features and the
                        decision is cached in the PlanCache.

    Tuning:
      tune:             "model" | "search" | "off" | TuneConfig
      tune_backend:     backend the empirical search times
      tune_n / tune_kf: dense width the tuner prices the TC/VPU split
                        at (SpMM B cols / SDDMM feature dim); each
                        call's lane tile follows its own width
      tune_cache:       PlanCache instance or cache-dir path

    Execution:
      backend:          default apply backend ("xla" | "pallas"); Pallas
                        kernels compile on a TPU and run in the Pallas
                        interpreter elsewhere
      b_layout:         dense-operand layout for sharded ops
                        ("replicated" | "rowshard")
    """

    mode: str = "hybrid"
    threshold: int | None = None
    sddmm_threshold: int | None = None
    bk: int | None = None
    ts_tile: int | None = None
    reorder: str = "off"
    tune: str | TuneConfig = "model"
    tune_backend: str = "xla"
    tune_n: int = 128
    tune_kf: int = 128
    tune_cache: Any = None
    backend: str = "xla"
    b_layout: str = "replicated"

    def __post_init__(self):
        if self.reorder not in _REORDER_MODES:
            raise ValueError(
                f"reorder must be one of {_REORDER_MODES}, got "
                f"{self.reorder!r}")
        if self.mode not in ("hybrid", "tcu", "vpu"):
            raise ValueError(f"unknown mode {self.mode!r}")

    def replace(self, **kw) -> "ExecSpec":
        return dataclasses.replace(self, **kw)

    def resolve(self, field: str, explicit=UNSET):
        """One knob, canonical order: explicit kwarg > spec field."""
        return getattr(self, field) if explicit is UNSET else explicit


# Call sites that already emitted their one legacy-kwarg warning.
_warned_sites: set[str] = set()


def reset_deprecation_warnings() -> None:
    """Forget which sites warned (test hook)."""
    _warned_sites.clear()


def warn_legacy(site: str, kwargs) -> None:
    """Emit the deprecation shim's warning, once per call site."""
    if site in _warned_sites:
        return
    _warned_sites.add(site)
    warnings.warn(
        f"{site}: keyword(s) {sorted(kwargs)} are deprecated — pass "
        f"spec=repro.api.ExecSpec(...) instead (legacy kwargs still "
        f"override the spec for now)",
        DeprecationWarning, stacklevel=3)


def resolve_spec(spec: ExecSpec | None, site: str, **legacy) -> ExecSpec:
    """Build the effective spec for one call.

    ``legacy`` maps spec field names to the values of that site's
    old-style kwargs (pass :data:`UNSET` for "not given"). Resolution
    is explicit kwarg > ``spec`` > :class:`ExecSpec` default; any
    explicitly-given legacy kwarg triggers the once-per-site
    :class:`DeprecationWarning`.
    """
    base = ExecSpec() if spec is None else spec
    used = {k: v for k, v in legacy.items() if v is not UNSET}
    if used:
        warn_legacy(site, used)
        base = dataclasses.replace(base, **used)
    return base
