"""Paper Fig. 9 / Tab. 4: SpMM throughput, Libra hybrid vs single-resource
modes vs framework baselines (dense jnp matmul, BCOO sparse), plus
tuned-vs-default rows for the autotuner (`repro.tune`) on the default
bench matrix."""
from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from benchmarks.common import corpus, spmm_gflops, timeit
from repro.core.spmm import LibraSpMM
from repro.kernels.ops import spmm_apply

N = 128


def _pallas_bytes_accessed(op: LibraSpMM, b) -> float:
    """HLO bytes-accessed of the jitted Pallas apply (compile only, no
    run) via the roofline analyzer — the redundant-output-traffic metric
    the single-pass fused path optimizes."""
    from repro.launch import hlo_analysis as H

    lowered = spmm_apply.lower(op.arrays, b, m=op.m, nwin=op.nwin,
                               backend="pallas", cfg=op.tune_config)
    return float(H.analyze_hlo(lowered.compile().as_text()).hbm_bytes)


def _tuned_rows(name: str, a, b, t_default: float) -> list[tuple]:
    """Tuned-vs-default rows on the default bench matrix: the analytical
    model pick and the (fresh-cache) empirical search pick, each as a
    speedup over the hardcoded-default config. Search always includes
    the default config as candidate #0, so x ≥ 1.0 up to timer noise;
    when search picks a config identical to the default the default's
    own measurement is reused (same executable)."""
    from repro.tune import PlanCache, occupancy_report, vmem_spmm_bytes

    rows = []
    op_m = LibraSpMM(a, tune="model")
    t_model = timeit(lambda: op_m(b))
    cfg = op_m.tune_config
    occ = occupancy_report(vmem_spmm_bytes(
        cfg, bk=op_m.plan.tc.bk, ts=op_m.plan.vpu.ts))
    rows.append((f"spmm/{name}/tuned_model", t_model * 1e6,
                 f"thr{cfg.threshold}_nt{cfg.nt}"
                 f"_vmem{occ['bytes_per_step'] // 1024}KB"
                 f"_x{t_default / t_model:.2f}"))
    with tempfile.TemporaryDirectory() as d:
        op_s = LibraSpMM(a, tune="search", tune_cache=PlanCache(d))
    cfg_s = op_s.tune_config
    from repro.core import preprocess as P

    # On the default XLA timing backend the executable depends only on
    # the plan parameters (tile fields are inert there) — when those
    # match the hardcoded defaults, reuse the default's measurement
    # instead of re-timing the identical executable.
    if (cfg_s.threshold == P.DEFAULT_SPMM_THRESHOLD
            and (cfg_s.bk or P.DEFAULT_BK_SPMM) == P.DEFAULT_BK_SPMM
            and (cfg_s.ts_tile or 32) == 32):
        t_search = t_default
    else:
        t_search = timeit(lambda: op_s(b))
    rows.append((f"spmm/{name}/tuned_search", t_search * 1e6,
                 f"thr{cfg_s.threshold}_nt{cfg_s.nt}"
                 f"_x{t_default / t_search:.2f}"))
    return rows


def _interleaved(f1, f2, reps: int = 9):
    """Median seconds of two callables timed back-to-back per rep, so
    machine drift (the dominant noise source for interpret-mode Pallas)
    cancels out of their ratio."""
    import time

    jax.block_until_ready(f1())
    jax.block_until_ready(f2())
    t1s, t2s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f1())
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f2())
        t2s.append(time.perf_counter() - t0)
    return float(np.median(t1s)), float(np.median(t2s))


def _segmented_rows() -> list[tuple]:
    """§4.3 hybrid load balancing on the kernel grid: a power-law
    *column*-degree matrix (graph in-degree skew — the transpose of the
    row-skew generator) packs many condensed TC blocks into its heavy
    windows; the Ts decomposition merges each window's blocks into
    bounded segments, so the Pallas TC stream runs ~4× fewer grid steps
    than one block per segment (``ts=1``) with no padding. ``tcu`` mode
    isolates that stream (the paper's single-resource ablation)."""
    from repro.models.gnn import transpose_csr
    from repro.sparse.generate import power_law_csr

    rng = np.random.default_rng(5)
    a_t, _ = transpose_csr(
        power_law_csr(512, 512, avg_row=32.0, alpha=1.3, seed=42))
    b = jnp.asarray(rng.standard_normal((a_t.k, N)).astype(np.float32))
    op = LibraSpMM(a_t, mode="tcu", tune="model")
    cfg = op.tune_config
    op1 = LibraSpMM(a_t, mode="tcu", tune=cfg.replace(ts=1, cs=cfg.ts_tile))
    t_seg, t_one = _interleaved(lambda: op(b, backend="pallas"),
                                lambda: op1(b, backend="pallas"))
    nseg = op.plan.meta["tc_segments"].nseg
    nblk = op1.plan.tc.nblk
    return [
        ("spmm/powerlaw_tr/tcu_segmented", t_seg * 1e6,
         f"ts{cfg.ts}_steps{nseg}of{nblk}_x{t_one / t_seg:.2f}"),
        ("spmm/powerlaw_tr/tcu_one_block_segments", t_one * 1e6,
         f"steps{nblk}"),
    ]


def _bit_identity_row(mats: dict) -> tuple:
    """Whole-corpus bit-identity of the tuned segment launch vs one block
    (tile) per segment and the XLA reference. Checked on integer-valued
    copies: float addition is exact there, so the segment
    re-association must be bitwise inert."""
    from repro.sparse.matrix import coo_to_csr

    rng = np.random.default_rng(11)
    ok = True
    for a in mats.values():
        ai = coo_to_csr(a.m, a.k, *a.to_coo()[:2],
                        rng.integers(1, 4, a.nnz).astype(np.float32))
        b = jnp.asarray(rng.integers(-2, 3, (a.k, 32)).astype(np.float32))
        op = LibraSpMM(ai, tune="model")
        cfg = op.tune_config
        op1 = LibraSpMM(ai, tune=cfg.replace(ts=1, cs=cfg.ts_tile))
        seg_p = np.asarray(op(b, backend="pallas"))
        ok &= np.array_equal(seg_p, np.asarray(op1(b, backend="pallas")))
        ok &= np.array_equal(seg_p, np.asarray(op(b, backend="xla")))
    return ("spmm/segmented_bit_identical", 0.0,
            f"{ok}_int_valued_{len(mats)}mats")


def run() -> list[tuple]:
    rows = []
    rng = np.random.default_rng(1)
    speedups_vs_dense = []
    speedups_vs_bcoo = []
    first = True
    for name, a in corpus().items():
        b = jnp.asarray(rng.standard_normal((a.k, N)).astype(np.float32))
        dense_a = jnp.asarray(a.to_dense())
        t_dense = timeit(jax.jit(lambda da, b: da @ b), dense_a, b)
        bcoo = jsparse.BCOO.fromdense(np.asarray(dense_a))
        t_bcoo = timeit(jax.jit(lambda m, b: m @ b), bcoo, b)
        results = {}
        ops = {}
        for mode in ("hybrid", "tcu", "vpu"):
            # tune="off" keeps these rows the hardcoded-default baseline
            # the tuned_* rows are measured against.
            op = LibraSpMM(a, mode=mode, tune="off")
            ops[mode] = op
            results[mode] = timeit(lambda: op(b))
        t_hyb = results["hybrid"]
        rows.append((f"spmm/{name}/hybrid", t_hyb * 1e6,
                     f"{spmm_gflops(a.nnz, N, t_hyb):.2f}GF"))
        rows.append((f"spmm/{name}/tcu_only", results["tcu"] * 1e6,
                     f"{spmm_gflops(a.nnz, N, results['tcu']):.2f}GF"))
        rows.append((f"spmm/{name}/vpu_only", results["vpu"] * 1e6,
                     f"{spmm_gflops(a.nnz, N, results['vpu']):.2f}GF"))
        rows.append((f"spmm/{name}/dense", t_dense * 1e6,
                     f"x{t_dense / t_hyb:.2f}"))
        rows.append((f"spmm/{name}/bcoo", t_bcoo * 1e6,
                     f"x{t_bcoo / t_hyb:.2f}"))
        speedups_vs_dense.append(t_dense / t_hyb)
        speedups_vs_bcoo.append(t_bcoo / t_hyb)
        if first:  # default matrix: fused-path memory + tuned-vs-default
            first = False
            rows.append((f"spmm/{name}/pallas_bytes_accessed", 0.0,
                         f"{_pallas_bytes_accessed(ops['hybrid'], b):.0f}B"))
            rows.extend(_tuned_rows(name, a, b, t_hyb))
    rows.append(("spmm/gmean_speedup_vs_dense", 0.0,
                 f"{np.exp(np.mean(np.log(speedups_vs_dense))):.2f}x"))
    rows.append(("spmm/gmean_speedup_vs_bcoo", 0.0,
                 f"{np.exp(np.mean(np.log(speedups_vs_bcoo))):.2f}x"))
    rows.extend(_segmented_rows())
    rows.append(_bit_identity_row(corpus()))
    # Row-reordering e2e rows ride in this suite's committed JSON too:
    # the speedup bar is what the bench-regression gate holds the pass to.
    from benchmarks import bench_reorder

    rows.extend(bench_reorder.run())
    return rows
