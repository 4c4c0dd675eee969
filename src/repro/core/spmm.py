"""Public hybrid SpMM: the paper's headline operator, end to end.

Usage::

    op = LibraSpMM(a_csr)            # preprocess + autotune once (§4.5)
    c = op(b)                        # reuse every iteration
    c = op(b, backend="pallas")      # run the TPU kernels (interpret on CPU)

Execution knobs live on one frozen :class:`repro.api.ExecSpec`::

    op = LibraSpMM(a, spec=ExecSpec(mode="tcu", tune="search",
                                    reorder="auto"))

Resolution order is explicit kwarg > spec > default; the legacy kwargs
(``mode=``, ``threshold=``, ``tune=`` …) keep working through a
deprecation shim that folds them into the spec (one
``DeprecationWarning`` per call site).

Single-resource ablation modes (paper §5.4.1) are exposed through the
threshold: ``mode="tcu"`` forces every vector to the MXU path,
``mode="vpu"`` forces everything to the VPU path, ``mode="hybrid"`` uses
the 2D-aware distribution.

Autotuning (``ExecSpec.tune``, paper §4.2's 2D-aware choices made
per matrix instead of hardcoded):

* ``tune="model"`` (default) — the analytical occupancy model in
  :mod:`repro.tune` picks the TC/VPU threshold from the matrix's vector
  histogram and sizes the ``nt`` cap to the VMEM budget.
  Cheap (one feature pass, no timing).
* ``tune="search"`` — empirically times a small candidate grid through
  this apply path and keeps the argmin; memoized in the persistent
  :class:`~repro.tune.cache.PlanCache` (``tune_cache=`` overrides the
  cache dir / instance) so re-constructing the same operator never
  re-times. The hardcoded default config is always a candidate, so
  search can't lose to it.
* ``tune="off"`` — the pre-tuner hardcoded defaults.
* ``tune=TuneConfig(...)`` — exactly that config (expert escape hatch).

An explicit ``threshold=`` (or a forcing ``mode=``) always wins over the
tuner's threshold; the tuner then only sizes tiles. ``tune_backend=``
selects which backend the search times (default ``"xla"``; pass
``"pallas"`` to let tile/grid-order candidates compete — on the XLA
reference path those fields are inert, so its candidate grid is
threshold-only). The chosen config is exposed as ``op.tune_config``.

``ExecSpec.reorder`` ("auto"/"on"/"off") runs the sparsity-aware row
reordering pass (:mod:`repro.reorder`) before planning; outputs are
unpermuted by one ``take`` in the apply epilogue and the permutation is
exposed as ``op.reorder`` for callers who keep permuted space.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.api import UNSET, ExecSpec, resolve_spec
from repro.core import preprocess
from repro.core.formats import WINDOW, SpMMPlan, device_arrays
from repro.core.windows import num_windows
from repro.kernels.ops import cached_compile, spmm_apply
from repro.obs.ledger import apply_sampler
from repro.sparse.matrix import SparseCSR
from repro.tune import TuneConfig

# Back-compat alias (the Literal lived here before ExecSpec).
Mode = str


def threshold_for_mode(mode: str, threshold: int | None = None) -> int:
    return preprocess.threshold_for_mode_spmm(mode, threshold)


class LibraSpMM:
    """Preprocess-once, apply-many hybrid SpMM operator."""

    def __init__(self, a: SparseCSR, mode=UNSET, threshold=UNSET,
                 bk=UNSET, ts_tile=UNSET, balance=None, tune=UNSET,
                 tune_cache=UNSET, tune_n=UNSET, tune_backend=UNSET,
                 reorder=UNSET, *, spec: ExecSpec | None = None):
        spec = resolve_spec(
            spec, "LibraSpMM", mode=mode, threshold=threshold, bk=bk,
            ts_tile=ts_tile, tune=tune, tune_cache=tune_cache,
            tune_n=tune_n, tune_backend=tune_backend, reorder=reorder)
        self.spec = spec
        self.m, self.k = a.shape
        self.nwin = num_windows(a.m)
        self.mode = spec.mode
        built = preprocess.Plan.build(a, "spmm", spec, balance=balance)
        self.tune_config: TuneConfig = built.cfg
        self.plan: SpMMPlan = built.plan
        self.reorder = built.reorder
        # One-gather unpermute epilogue: reordered output row
        # row_inv[j] is original row j (see repro.reorder).
        self._row_unperm = (None if built.reorder is None
                            else jnp.asarray(built.reorder.row_inv))
        self.arrays = device_arrays(self.plan)
        # Per-operator AOT apply cache keyed (n, dtype, backend, ...) —
        # see kernels.ops.cached_compile.
        self._apply_cache: dict = {}
        # Perf-ledger context: the matrix the plan was actually built on
        # (reordered view when reordering applied — its signature is
        # what search entries were cached under) and the
        # tune-resolution inputs, so recorded samples can carry the
        # PlanCache key drift staling targets. Nothing here is touched
        # unless a ledger is active.
        self._a = built.a
        forced = (threshold_for_mode(spec.mode, spec.threshold)
                  if spec.mode != "hybrid" else spec.threshold)
        self._tune_ctx = dict(
            mode=spec.mode,
            tune=spec.tune if isinstance(spec.tune, str) else None,
            threshold=forced, bk=spec.bk, ts_tile=spec.ts_tile,
            width=spec.tune_n, dtype="float32",
            backend=spec.tune_backend)

    def __call__(self, b: jnp.ndarray, backend: str | None = None
                 ) -> jnp.ndarray:
        assert b.shape[0] == self.k, (b.shape, self.k)
        backend = self.spec.backend if backend is None else backend
        # Only the key set the apply reads is uploaded: the §4.3
        # segment tables, never their revaluation maps.
        arrs = self.arrays.apply_arrays()
        fn = cached_compile(
            self._apply_cache,
            (b.shape[1], str(b.dtype), backend),
            lambda: spmm_apply.lower(arrs, b, m=self.m,
                                     nwin=self.nwin, backend=backend,
                                     cfg=self.tune_config),
            sample=apply_sampler(self, "spmm", width=b.shape[1],
                                 dtype=str(b.dtype), backend=backend))
        out = fn(arrs, b)
        if self._row_unperm is not None:
            out = jnp.take(out, self._row_unperm, axis=0)
        return out

    @property
    def tc_ratio(self) -> float:
        """Fraction of non-zeros handled by the MXU path (paper Fig. 1)."""
        return self.plan.meta["tc_ratio"]
