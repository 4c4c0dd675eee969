"""Paper Fig. 10 / Tab. 6: SDDMM throughput, hybrid vs single-resource
vs dense sampled baseline. N (feature width) = 32 as in the paper."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import corpus, sddmm_gflops, timeit
from repro.core.sddmm import LibraSDDMM

K = 32


def run() -> list[tuple]:
    rows = []
    rng = np.random.default_rng(2)
    ups = []
    first = True
    for name, a in corpus().items():
        x = jnp.asarray(rng.standard_normal((a.m, K)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((a.k, K)).astype(np.float32))
        r, c, _ = a.to_coo()
        ri, ci = jnp.asarray(r), jnp.asarray(c)

        def dense_sampled(x, y):
            return (x @ y.T)[ri, ci]

        t_dense = timeit(jax.jit(dense_sampled), x, y)
        res = {}
        for mode in ("hybrid", "tcu", "vpu"):
            op = LibraSDDMM(a, mode=mode, tune="off")
            res[mode] = timeit(lambda: op(x, y))
        t_h = res["hybrid"]
        if first:  # default matrix: model-tuned vs hardcoded defaults
            first = False
            op_m = LibraSDDMM(a, tune="model", tune_kf=K)
            t_m = timeit(lambda: op_m(x, y))
            cfg = op_m.tune_config
            rows.append((f"sddmm/{name}/tuned_model", t_m * 1e6,
                         f"thr{cfg.threshold}_kf{cfg.kf_tile}"
                         f"_x{t_h / t_m:.2f}"))
        rows.append((f"sddmm/{name}/hybrid", t_h * 1e6,
                     f"{sddmm_gflops(a.nnz, K, t_h):.2f}GF"))
        rows.append((f"sddmm/{name}/tcu_only", res["tcu"] * 1e6,
                     f"{sddmm_gflops(a.nnz, K, res['tcu']):.2f}GF"))
        rows.append((f"sddmm/{name}/vpu_only", res["vpu"] * 1e6,
                     f"{sddmm_gflops(a.nnz, K, res['vpu']):.2f}GF"))
        rows.append((f"sddmm/{name}/dense_sampled", t_dense * 1e6,
                     f"x{t_dense / t_h:.2f}"))
        ups.append(t_dense / t_h)
    rows.append(("sddmm/gmean_speedup_vs_dense", 0.0,
                 f"{np.exp(np.mean(np.log(ups))):.2f}x"))
    return rows
