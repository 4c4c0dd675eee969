"""Public hybrid SDDMM: values = sample(X·Yᵀ, sparsity(A)).

Output follows the canonical CSR (row-major, column-sorted) non-zero
order of the mask matrix, so GNN attention pipelines can chain
``SDDMM → softmax-by-row → SpMM`` without reindexing — this holds even
under ``ExecSpec.reorder``: the plan's scatter maps are rewritten back
to original-canonical positions at build time, and the row-permuted
``x`` operand is gathered once on the way in.

Execution knobs live on one frozen :class:`repro.api.ExecSpec`
(``spec=``; legacy kwargs keep working via the deprecation shim — the
SDDMM block threshold maps to ``ExecSpec.sddmm_threshold``). Autotuning
semantics (``spec.tune``) match :class:`repro.core.spmm.LibraSpMM`:
``"model"`` (default) picks the block threshold from the matrix's
vector histogram and sizes the feature tile (``kf_tile``) to the VMEM
budget; ``"search"`` times a candidate grid
and memoizes the winner in the persistent plan cache; ``"off"`` keeps
the hardcoded defaults; a :class:`~repro.tune.model.TuneConfig`
instance is used as-is. Explicit ``threshold=``/forcing ``mode=``
always win over the tuner's threshold. The chosen config is exposed as
``op.tune_config``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.api import UNSET, ExecSpec, resolve_spec
from repro.core import preprocess
from repro.core.formats import SDDMMPlan, device_arrays
from repro.kernels.ops import cached_compile, sddmm_apply
from repro.obs.ledger import apply_sampler
from repro.sparse.matrix import SparseCSR
from repro.tune import TuneConfig


def threshold_for_mode(mode: str, bk: int, threshold: int | None = None) -> int:
    return preprocess.threshold_for_mode_sddmm(mode, bk, threshold)


class LibraSDDMM:
    """Preprocess-once, apply-many hybrid SDDMM operator."""

    def __init__(self, a: SparseCSR, mode=UNSET, threshold=UNSET,
                 bk=UNSET, ts_tile=UNSET, balance=None, tune=UNSET,
                 tune_cache=UNSET, tune_kf=UNSET, tune_backend=UNSET,
                 reorder=UNSET, *, spec: ExecSpec | None = None):
        spec = resolve_spec(
            spec, "LibraSDDMM", mode=mode, sddmm_threshold=threshold,
            bk=bk, ts_tile=ts_tile, tune=tune, tune_cache=tune_cache,
            tune_kf=tune_kf, tune_backend=tune_backend, reorder=reorder)
        self.spec = spec
        self.m, self.k = a.shape
        self.nnz = a.nnz
        self.mode = spec.mode
        built = preprocess.Plan.build(a, "sddmm", spec, balance=balance)
        self.tune_config: TuneConfig = built.cfg
        self.plan: SDDMMPlan = built.plan
        self.reorder = built.reorder
        # The SDDMM output scatter maps were rewritten to original
        # canonical positions at build time, so only the row operand
        # needs permuting: x_reordered = x[row_perm].
        self._row_perm = (None if built.reorder is None
                          else jnp.asarray(built.reorder.row_perm))
        self.arrays = device_arrays(self.plan)
        # CSR structure for chaining into softmax/SpMM — always the
        # *original* matrix's (outputs land in its canonical order).
        self.indptr = np.asarray(a.indptr)
        self.indices = np.asarray(a.indices)
        # Per-operator AOT apply cache keyed (kf, dtype, backend, ...) —
        # see kernels.ops.cached_compile.
        self._apply_cache: dict = {}
        # Perf-ledger context (see LibraSpMM): untouched unless a ledger
        # is active.
        self._a = built.a
        bk_eff = preprocess.DEFAULT_BK_SDDMM if spec.bk is None else spec.bk
        forced = (threshold_for_mode(spec.mode, bk_eff, spec.sddmm_threshold)
                  if spec.mode != "hybrid" else spec.sddmm_threshold)
        self._tune_ctx = dict(
            mode=spec.mode,
            tune=spec.tune if isinstance(spec.tune, str) else None,
            threshold=forced, bk=spec.bk, ts_tile=spec.ts_tile,
            width=spec.tune_kf, dtype="float32",
            backend=spec.tune_backend)

    def __call__(self, x: jnp.ndarray, y: jnp.ndarray,
                 backend: str | None = None) -> jnp.ndarray:
        assert x.shape[0] >= self.m and y.shape[0] >= self.k
        backend = self.spec.backend if backend is None else backend
        if self._row_perm is not None:
            # Row-permuted plan: gather x into reordered row space (the
            # output scatter maps already point back to original
            # canonical nnz order). Padding rows past m stay in place.
            perm = self._row_perm
            if x.shape[0] > self.m:
                perm = jnp.concatenate(
                    [perm, jnp.arange(self.m, x.shape[0])])
            x = jnp.take(x, perm, axis=0)
        # Lazy view: see LibraSpMM.__call__.
        arrs = self.arrays.apply_arrays()
        fn = cached_compile(
            self._apply_cache,
            (x.shape[1], str(x.dtype), backend, x.shape[0], y.shape[0]),
            lambda: sddmm_apply.lower(arrs, x, y, nnz=self.nnz,
                                      backend=backend, cfg=self.tune_config),
            sample=apply_sampler(self, "sddmm", width=x.shape[1],
                                 dtype=str(x.dtype), backend=backend))
        return fn(arrs, x, y)

    @property
    def tc_ratio(self) -> float:
        return self.plan.meta["tc_ratio"]
