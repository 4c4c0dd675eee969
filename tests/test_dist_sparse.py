"""Window-sharded + batched sparse execution (`repro.dist`).

Host-side invariants (partition geometry, halo maps, batched-vs-looped
equivalence, 1-shard transparency) run in-process on the suite's single
device; everything needing a real mesh runs in a forced-8-device
subprocess (same pattern as test_distributed.py)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import WINDOW
from repro.core.windows import num_windows
from repro.dist import (
    BatchedSDDMM,
    BatchedSpMM,
    column_halo,
    partition_sddmm,
    partition_spmm,
    shard_windows,
)
from repro.sparse.generate import mixed_csr
from repro.sparse import power_law_csr

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------ partition (host) ---
def test_shard_windows_contiguous_cover_and_balance():
    a = power_law_csr(400, 300, 6.0, seed=3)
    nwin = num_windows(a.m)
    for p in (1, 3, 8):
        bounds = shard_windows(a, p)
        assert bounds[0] == 0 and bounds[-1] == nwin
        assert np.all(np.diff(bounds) >= 0)
        # nnz balance: each shard within one window's nnz of the ideal
        win_nnz = np.diff(a.indptr[np.minimum(
            np.arange(nwin + 1) * WINDOW, a.m)])
        shard_nnz = np.asarray([
            int(win_nnz[bounds[i]:bounds[i + 1]].sum()) for i in range(p)])
        assert shard_nnz.sum() == a.nnz
        assert shard_nnz.max() <= a.nnz / p + win_nnz.max()


def test_column_halo_invariants():
    a = mixed_csr(120, 96, seed=7)
    bounds = shard_windows(a, 4)
    rows_seen = 0
    nnz_seen = 0
    for i in range(4):
        r0 = min(int(bounds[i]) * WINDOW, a.m)
        r1 = max(min(int(bounds[i + 1]) * WINDOW, a.m), r0)
        halo, sub = column_halo(a, r0, r1)
        # sorted unique, exactly the touched B rows
        assert np.all(np.diff(halo) > 0)
        lo, hi = int(a.indptr[r0]), int(a.indptr[r1])
        np.testing.assert_array_equal(np.unique(a.indices[lo:hi]), halo)
        # the remap round-trips and preserves canonical order + values
        np.testing.assert_array_equal(halo[sub.indices], a.indices[lo:hi])
        np.testing.assert_allclose(sub.data, a.data[lo:hi])
        rows_seen += sub.m
        nnz_seen += sub.nnz
    assert rows_seen == a.m and nnz_seen == a.nnz


def test_partition_global_gather_maps():
    a = mixed_csr(120, 96, seed=8)
    part = partition_spmm(a, 4, tune="off")
    # out_gather is a bijection global row -> (shard, local slot)
    og = np.asarray(part.out_gather)
    assert og.shape == (a.m,) and np.unique(og).size == a.m
    sd = partition_sddmm(a, 4, tune="off")
    ng = np.asarray(sd.nnz_gather)
    assert ng.shape == (a.nnz,) and np.unique(ng).size == a.nnz
    # per-shard tuned configs exist and block geometry is unified
    assert len({s.cfg.bk for s in part.shards}) == 1
    assert len({s.cfg.ts_tile for s in part.shards}) == 1
    assert part.meta["balance"]["max_over_mean"] >= 1.0


@pytest.mark.parametrize("op", ["spmm", "sddmm"])
def test_partition_search_keeps_the_model_caps(rng, op):
    """A partition's run_cfg holds only lane-tile caps, a VMEM bound, so
    ``tune="search"`` builds the model-tuned partition: the same
    run_cfg, the model's caps, and the right answer on a mesh."""
    from repro.dist import sddmm_sharded, spmm_sharded
    from repro.kernels import ref

    a = mixed_csr(96, 80, seed=10)
    build = partition_spmm if op == "spmm" else partition_sddmm
    part = build(a, 1, tune="search", tune_backend="pallas")
    assert part.run_cfg == build(a, 1, tune="model").run_cfg
    assert (part.run_cfg.nt, part.run_cfg.kf_tile) == (
        part.shards[0].cfg.nt, part.shards[0].cfg.kf_tile)

    mesh = jax.make_mesh((1,), ("shards",))
    x = rng.standard_normal((a.m, 24)).astype(np.float32)
    y = rng.standard_normal((a.k, 24)).astype(np.float32)
    if op == "spmm":
        got = spmm_sharded(part, jnp.asarray(y), mesh=mesh, backend="pallas")
        want = a.to_dense() @ y
    else:
        got = sddmm_sharded(part, jnp.asarray(x), jnp.asarray(y), mesh=mesh,
                            backend="pallas")
        want = ref.sddmm_dense_oracle(a.to_dense(), x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_single_shard_partition_is_transparent(rng):
    """P=1 on the suite's single device: sharded == plain fused apply."""
    from repro.core.spmm import LibraSpMM
    from repro.dist import spmm_sharded

    a = mixed_csr(80, 72, seed=10)
    mesh = jax.make_mesh((1,), ("shards",))
    part = partition_spmm(a, 1, tune="model")
    b = jnp.asarray(rng.standard_normal((a.k, 24)).astype(np.float32))
    got = np.asarray(spmm_sharded(part, b, mesh=mesh))
    want = np.asarray(LibraSpMM(a, tune="model")(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------- batched (host) ---
def test_batched_spmm_matches_loop_bitwise(rng):
    a = mixed_csr(96, 80, seed=11)
    bop = BatchedSpMM(a, tune="model")
    bb = jnp.asarray(rng.standard_normal((4, a.k, 32)).astype(np.float32))
    for backend in ("xla", "pallas"):
        got = np.asarray(bop(bb, backend=backend))
        loop = np.stack([np.asarray(bop.op(bb[i], backend=backend))
                         for i in range(bb.shape[0])])
        assert np.array_equal(got, loop), backend
    # one executable per shape: the second call is a cache hit
    assert len(bop._cache) == 2
    bop(bb)
    assert len(bop._cache) == 2


def test_batched_sddmm_matches_loop_bitwise(rng):
    a = mixed_csr(88, 96, seed=12)
    sop = BatchedSDDMM(a, tune="model")
    xx = jnp.asarray(rng.standard_normal((3, a.m, 24)).astype(np.float32))
    yy = jnp.asarray(rng.standard_normal((3, a.k, 24)).astype(np.float32))
    for backend in ("xla", "pallas"):
        got = np.asarray(sop(xx, yy, backend=backend))
        loop = np.stack([np.asarray(sop.op(xx[i], yy[i], backend=backend))
                         for i in range(xx.shape[0])])
        assert np.array_equal(got, loop), backend


# ------------------------------------------------------- 8-device (mesh) ---
def test_sharded_ops_match_oracle_8dev():
    """All modes × both dense layouts × both backends on an 8-way mesh,
    including a matrix with empty shards (P > nwin)."""
    out = run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.dist import (partition_spmm, partition_sddmm,
                                spmm_sharded, sddmm_sharded)
        from repro.sparse.generate import mixed_csr
        from repro.kernels import ref
        assert jax.device_count() == 8
        mesh = jax.make_mesh((8,), ("shards",))
        rng = np.random.default_rng(0)
        for m, k in ((200, 160), (40, 64)):   # 40 rows -> 5 windows < 8
            a = mixed_csr(m, k, seed=5)
            b = jnp.asarray(rng.standard_normal((a.k, 48)).astype(np.float32))
            dense = a.to_dense()
            for mode in ("hybrid", "tcu", "vpu"):
                part = partition_spmm(a, 8, mode=mode, tune="model")
                for layout in ("replicated", "rowshard"):
                    c = spmm_sharded(part, b, mesh=mesh, b_layout=layout)
                    np.testing.assert_allclose(np.asarray(c),
                        dense @ np.asarray(b), rtol=1e-4, atol=1e-4)
                c = spmm_sharded(part, b, mesh=mesh, backend="pallas")
                np.testing.assert_allclose(np.asarray(c),
                    dense @ np.asarray(b), rtol=1e-4, atol=1e-4)
            x = jnp.asarray(rng.standard_normal((a.m, 32)).astype(np.float32))
            y = jnp.asarray(rng.standard_normal((a.k, 32)).astype(np.float32))
            oracle = ref.sddmm_dense_oracle(dense, np.asarray(x), np.asarray(y))
            for mode in ("hybrid", "tcu", "vpu"):
                part = partition_sddmm(a, 8, mode=mode, tune="model")
                for layout in ("replicated", "rowshard"):
                    v = sddmm_sharded(part, x, y, mesh=mesh, y_layout=layout)
                    np.testing.assert_allclose(np.asarray(v), oracle,
                                               rtol=1e-4, atol=1e-4)
                v = sddmm_sharded(part, x, y, mesh=mesh, backend="pallas")
                np.testing.assert_allclose(np.asarray(v), oracle,
                                           rtol=1e-4, atol=1e-4)
        # revalue path (training values) through the sharded apply
        a = mixed_csr(200, 160, seed=5)
        part = partition_spmm(a, 8, tune="model")
        b = jnp.asarray(rng.standard_normal((a.k, 16)).astype(np.float32))
        vals = jnp.asarray(rng.standard_normal(a.nnz).astype(np.float32))
        rows, cols, _ = a.to_coo()
        dv = np.zeros((a.m, a.k), np.float32); dv[rows, cols] = np.asarray(vals)
        c = spmm_sharded(part, b, mesh=mesh, edge_vals=vals)
        np.testing.assert_allclose(np.asarray(c), dv @ np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        print("SHARDED_OK")
    """)
    assert "SHARDED_OK" in out


def test_dist_graphops_grads_and_training_8dev():
    """DistGraphOps grads == GraphOps grads; multi-device GCN training
    loss trajectory matches single-device; AGNN step runs and learns."""
    out = run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.dist import DistGraphOps, make_gcn_train_step, \
            make_agnn_train_step
        from repro.models import gnn
        from repro.sparse.generate import mixed_csr
        a = mixed_csr(96, 96, seed=21)
        mesh = jax.make_mesh((8,), ("shards",))
        rng = np.random.default_rng(0)
        g1 = gnn.GraphOps(a)
        gd = DistGraphOps(a, mesh)
        vals = jnp.asarray(a.to_coo()[2])
        b = jnp.asarray(rng.standard_normal((a.k, 16)).astype(np.float32))
        ga = jax.grad(lambda v, b: (g1.spmm(v, b) ** 2).sum(),
                      argnums=(0, 1))(vals, b)
        gb = jax.grad(lambda v, b: (gd.spmm(v, b) ** 2).sum(),
                      argnums=(0, 1))(vals, b)
        for u, w in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        x = jnp.asarray(rng.standard_normal((a.m, 8)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((a.k, 8)).astype(np.float32))
        ga = jax.grad(lambda x, y: (g1.sddmm(x, y) ** 2).sum(),
                      argnums=(0, 1))(x, y)
        gb = jax.grad(lambda x, y: (gd.sddmm(x, y) ** 2).sum(),
                      argnums=(0, 1))(x, y)
        for u, w in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        feats = jnp.asarray(rng.standard_normal((a.m, 16)).astype(np.float32))
        labels = jnp.asarray(rng.integers(0, 4, a.m))
        norm = jnp.asarray(gnn.gcn_norm_edges(a))
        params = gnn.init_gcn(jax.random.PRNGKey(0), [16, 16, 4])
        step_s = make_gcn_train_step(g1, lr=0.3)
        step_d = make_gcn_train_step(gd, lr=0.3)
        ps = pd = params
        for _ in range(5):
            ps, ls = step_s(ps, feats, labels, norm)
            pd, ld = step_d(pd, feats, labels, norm)
        assert abs(float(ls) - float(ld)) < 1e-4, (float(ls), float(ld))
        pa = gnn.init_agnn(jax.random.PRNGKey(1), [16, 4])
        astep = make_agnn_train_step(gd, lr=0.2)
        losses = []
        for _ in range(3):
            pa, la = astep(pa, feats, labels)
            losses.append(float(la))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        print("DIST_TRAIN_OK", float(ls), float(ld))
    """)
    assert "DIST_TRAIN_OK" in out
