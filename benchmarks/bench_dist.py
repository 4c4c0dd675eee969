"""Distributed + batched sparse execution benchmarks (`BENCH_dist.json`).

Runs in the calling process on the devices JAX already sees: the shard
mesh spans ``jax.devices()``. On a CPU host, set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before starting
Python to get an 8-device mesh; the process never starts a JAX child
(a chip belongs to the one process that opened it).

On a CPU host the "devices" share the same cores, so sharded wall-clock
is a correctness/overhead trail, not a speedup claim — the derived
column records the ratio honestly. The batched rows quantify the win on
any backend: one AOT executable over a panel stack vs a Python loop of
single applies.
"""
from __future__ import annotations


def run() -> list[tuple]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import spmm_gflops, timeit
    from repro.core.spmm import LibraSpMM
    from repro.dist import (
        BatchedSpMM,
        DistGraphOps,
        make_gcn_train_step,
        partition_sddmm,
        partition_spmm,
        sddmm_sharded,
        spmm_sharded,
    )
    from repro.models import gnn
    from repro.sparse import power_law_csr

    rows = []
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("shards",))
    a = power_law_csr(2048, 2048, 16.0, seed=12)
    rng = np.random.default_rng(0)
    n = 128
    b = jnp.asarray(rng.standard_normal((a.k, n)).astype(np.float32))

    # --- sharded SpMM vs the single-device fused apply
    op = LibraSpMM(a, tune="model")
    t_single = timeit(lambda bb: op(bb), b)
    rows.append(("dist/spmm_single", t_single * 1e6,
                 f"{spmm_gflops(a.nnz, n, t_single):.2f}GF"))
    part = partition_spmm(a, n_dev, tune="model")
    fn = jax.jit(lambda bb: spmm_sharded(part, bb, mesh=mesh))
    t_shard = timeit(fn, b)
    rows.append((f"dist/spmm_sharded_p{n_dev}", t_shard * 1e6,
                 f"x{t_single / t_shard:.2f}_bal"
                 f"{part.meta['balance']['max_over_mean']:.2f}"))
    fn_rs = jax.jit(lambda bb: spmm_sharded(part, bb, mesh=mesh,
                                            b_layout="rowshard"))
    t_rs = timeit(fn_rs, b)
    rows.append((f"dist/spmm_sharded_p{n_dev}_rowshard", t_rs * 1e6,
                 f"x{t_single / t_rs:.2f}"))

    # --- sharded SDDMM
    from repro.core.sddmm import LibraSDDMM

    kf = 128
    x = jnp.asarray(rng.standard_normal((a.m, kf)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((a.k, kf)).astype(np.float32))
    sd = LibraSDDMM(a, tune="model")
    t_sd1 = timeit(lambda xx, yy: sd(xx, yy), x, y)
    rows.append(("dist/sddmm_single", t_sd1 * 1e6, ""))
    part_sd = partition_sddmm(a, n_dev, tune="model")
    fn_sd = jax.jit(lambda xx, yy: sddmm_sharded(part_sd, xx, yy, mesh=mesh))
    t_sds = timeit(fn_sd, x, y)
    rows.append((f"dist/sddmm_sharded_p{n_dev}", t_sds * 1e6,
                 f"x{t_sd1 / t_sds:.2f}"))

    # --- batched panels: one executable vs a Python loop
    batch = 8
    bb = jnp.asarray(
        rng.standard_normal((batch, a.k, n)).astype(np.float32))
    bop = BatchedSpMM(a, tune="model")
    bop(bb)  # compile
    t_batch = timeit(lambda s: bop(s), bb)
    t_loop = timeit(
        lambda s: [jax.block_until_ready(bop.op(s[i])) for i in range(batch)],
        bb)
    rows.append((f"dist/spmm_batched_b{batch}", t_batch * 1e6,
                 f"{spmm_gflops(a.nnz * batch, n, t_batch):.2f}GF"))
    rows.append((f"dist/spmm_batchloop_b{batch}", t_loop * 1e6,
                 f"batched_x{t_loop / t_batch:.2f}_vs_loop"))

    # --- multi-device GCN step vs single-device (loss parity as derived)
    g_small = power_law_csr(512, 512, 8.0, seed=13)
    feats = jnp.asarray(rng.standard_normal((g_small.m, 32)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 8, g_small.m))
    norm = jnp.asarray(gnn.gcn_norm_edges(g_small))
    params = gnn.init_gcn(jax.random.PRNGKey(0), [32, 32, 8])
    g1 = gnn.GraphOps(g_small, tune="model")
    gd = DistGraphOps(g_small, mesh, tune="model")
    step_s = make_gcn_train_step(g1, lr=0.2)
    step_d = make_gcn_train_step(gd, lr=0.2)
    ps = pd = params
    for _ in range(5):
        ps, loss_s = step_s(ps, feats, labels, norm)
        pd, loss_d = step_d(pd, feats, labels, norm)
    t_step_s = timeit(lambda p: step_s(p, feats, labels, norm)[1], ps)
    t_step_d = timeit(lambda p: step_d(p, feats, labels, norm)[1], pd)
    gap = abs(float(loss_s) - float(loss_d))
    rows.append(("dist/gcn_step_single", t_step_s * 1e6,
                 f"loss{float(loss_s):.4f}"))
    rows.append((f"dist/gcn_step_dist_p{n_dev}", t_step_d * 1e6,
                 f"loss{float(loss_d):.4f}_gap{gap:.1e}"))

    return rows


if __name__ == "__main__":
    for row in run():
        print(",".join(str(c) for c in row))
