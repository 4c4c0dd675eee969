"""Empirical tuner: time a small candidate grid through the real apply
path and keep the argmin (the paper's Fig.-11 protocol, generalized from
the threshold alone to the whole :class:`TuneConfig`).

The grid is deliberately tiny — the *hardcoded default* plan, the
analytical model's pick, and a handful of segment-cap/threshold
perturbations around it — because every candidate pays a full
preprocess + compile. The default plan is always candidate #0 and ties
resolve to the earliest candidate, so search can never lose to the
defaults it replaces. Results are meant to be memoized through
:class:`repro.tune.cache.PlanCache` (see :func:`repro.tune.tune_spmm`).

Timing is injectable (``timer(fn) -> seconds``) so tests drive the
search with a deterministic stub; the default timer is median wall time
after a compile/warmup call.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from repro.obs.ledger import get_ledger, record_apply
from repro.obs.trace import get_tracer
from repro.sparse.matrix import SparseCSR
from repro.tune.model import (
    DEFAULT_TUNE,
    TuneConfig,
    model_tune_sddmm,
    model_tune_spmm,
)

Timer = Callable[[Callable[[], object]], float]


def median_timer(reps: int = 3, warmup: int = 1) -> Timer:
    def timer(fn: Callable[[], object]) -> float:
        for _ in range(warmup):
            jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))
    return timer


def _dedup(cands: list[TuneConfig]) -> list[TuneConfig]:
    seen, out = set(), []
    for c in cands:
        key = c.replace(source="x")
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def spmm_candidates(a: SparseCSR, *, n: int, mode: str,
                    threshold: int | None, backend: str = "xla",
                    bk: int | None = None,
                    ts_tile: int | None = None) -> list[TuneConfig]:
    """Candidate grid, shaped by what the timed backend can distinguish.

    Candidate #0 is the floor search can't lose to: the hardcoded
    default *plan* (default threshold/bk/ts_tile — plan parameters are
    read on every backend). On ``"xla"`` its segment caps ride on the
    model's deterministic sizing, which times identically (the
    reference path never reads them); on ``"pallas"`` they are the
    default config's. Segment-cap perturbations are only emitted for
    ``"pallas"``, where they change the executable — on ``"xla"``
    they'd compile identically and the argmin over them would be pure
    timer noise. Every candidate carries the model's lane-tile cap: the
    cap is a VMEM bound, not a timed choice (each call's tile follows
    its own width, :func:`repro.tune.model.lane_tile`), and at the
    timed width ``n`` caps above it build the same program.
    """
    from repro.core import preprocess as P

    model = model_tune_spmm(a, n=n, mode=mode, threshold=threshold,
                            bk=bk, ts_tile=ts_tile)
    default_thr = (threshold if threshold is not None
                   else P.DEFAULT_SPMM_THRESHOLD)
    default_plan = {"threshold": default_thr, "bk": bk, "ts_tile": ts_tile}
    if backend == "xla":
        cands = [model.replace(**default_plan), model]
    else:
        cands = [DEFAULT_TUNE.replace(nt=model.nt, **default_plan), model]
        cands.extend(_seg_cap_perturbations(model))
    if threshold is None and mode == "hybrid" and model.threshold is not None:
        for t in (model.threshold - 1, model.threshold + 1):
            if 1 <= t <= 9:
                cands.append(model.replace(threshold=t))
    return _dedup(cands)


def _seg_cap_perturbations(model: TuneConfig) -> list[TuneConfig]:
    """§4.3 Ts/Cs cap perturbations around the model's pick. Segment
    caps re-layout the plan (the launch tables change), so they only
    matter where the executable iterates them — the Pallas backend."""
    out = []
    if model.ts is not None and model.ts > 0:
        for ts in (max(model.ts // 2, 1), min(model.ts * 2, 64)):
            if ts != model.ts:
                out.append(model.replace(ts=ts))
    if model.cs is not None and model.cs > 0:
        tile = model.ts_tile or 32
        for cs in (max(model.cs // 2, tile), min(model.cs * 2, 16 * tile)):
            if cs != model.cs:
                out.append(model.replace(cs=cs))
    return out


def sddmm_candidates(a: SparseCSR, *, kf: int, mode: str,
                     threshold: int | None, backend: str = "xla",
                     bk: int | None = None,
                     ts_tile: int | None = None) -> list[TuneConfig]:
    """See :func:`spmm_candidates` for the backend-shaped grid rationale."""
    from repro.core import preprocess as P

    model = model_tune_sddmm(a, kf=kf, mode=mode, threshold=threshold,
                             bk=bk, ts_tile=ts_tile)
    default_thr = (threshold if threshold is not None
                   else P.DEFAULT_SDDMM_THRESHOLD)
    default_plan = {"threshold": default_thr, "bk": bk, "ts_tile": ts_tile}
    if backend == "xla":
        cands = [model.replace(**default_plan), model]
    else:
        cands = [DEFAULT_TUNE.replace(kf_tile=model.kf_tile,
                                      **default_plan), model]
        cands.extend(_seg_cap_perturbations(model))
    if threshold is None and mode == "hybrid" and model.threshold is not None:
        for t in (max(model.threshold // 2, 1), model.threshold * 2):
            cands.append(model.replace(threshold=t))
    return _dedup(cands)


def search_spmm(a: SparseCSR, *, n: int = 128, backend: str = "xla",
                mode: str = "hybrid", threshold: int | None = None,
                candidates: list[TuneConfig] | None = None,
                timer: Timer | None = None, bk: int | None = None,
                ts_tile: int | None = None,
                seed: int = 0) -> tuple[TuneConfig, dict[int, float]]:
    """Time each candidate through ``LibraSpMM.__call__``; return the
    argmin config (``source="search"``) and per-candidate seconds."""
    from repro.core.spmm import LibraSpMM

    candidates = candidates if candidates is not None else spmm_candidates(
        a, n=n, mode=mode, threshold=threshold, backend=backend, bk=bk,
        ts_tile=ts_tile)
    timer = timer or median_timer()
    rng = np.random.default_rng(seed)
    b = jax.numpy.asarray(rng.standard_normal((a.k, n)).astype(np.float32))
    best_i, timings = 0, {}
    with get_tracer().span("tune.search", op="spmm", backend=backend,
                           candidates=len(candidates)) as sp:
        for i, cand in enumerate(candidates):
            op = LibraSpMM(a, mode=mode, threshold=cand.threshold,
                           tune=cand)
            timings[i] = timer(lambda: op(b, backend=backend))
            sp.event("candidate", index=i, threshold=cand.threshold,
                     seconds=timings[i])
            if get_ledger() is not None:
                record_apply(op, "spmm", width=n, dtype="float32",
                             backend=backend, wall_s=timings[i],
                             source="search")
            if timings[i] < timings[best_i]:
                best_i = i
        sp.set(best=best_i, best_seconds=timings[best_i])
    return candidates[best_i].replace(source="search"), timings


def search_sddmm(a: SparseCSR, *, kf: int = 128, backend: str = "xla",
                 mode: str = "hybrid", threshold: int | None = None,
                 candidates: list[TuneConfig] | None = None,
                 timer: Timer | None = None, bk: int | None = None,
                 ts_tile: int | None = None,
                 seed: int = 0) -> tuple[TuneConfig, dict[int, float]]:
    from repro.core.sddmm import LibraSDDMM

    candidates = candidates if candidates is not None else sddmm_candidates(
        a, kf=kf, mode=mode, threshold=threshold, backend=backend, bk=bk,
        ts_tile=ts_tile)
    timer = timer or median_timer()
    rng = np.random.default_rng(seed)
    x = jax.numpy.asarray(rng.standard_normal((a.m, kf)).astype(np.float32))
    y = jax.numpy.asarray(rng.standard_normal((a.k, kf)).astype(np.float32))
    best_i, timings = 0, {}
    with get_tracer().span("tune.search", op="sddmm", backend=backend,
                           candidates=len(candidates)) as sp:
        for i, cand in enumerate(candidates):
            op = LibraSDDMM(a, mode=mode, threshold=cand.threshold,
                            tune=cand)
            timings[i] = timer(lambda: op(x, y, backend=backend))
            sp.event("candidate", index=i, threshold=cand.threshold,
                     seconds=timings[i])
            if get_ledger() is not None:
                record_apply(op, "sddmm", width=kf, dtype="float32",
                             backend=backend, wall_s=timings[i],
                             source="search")
            if timings[i] < timings[best_i]:
                best_i = i
        sp.set(best=best_i, best_seconds=timings[best_i])
    return candidates[best_i].replace(source="search"), timings
