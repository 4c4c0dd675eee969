"""End-to-end GNN training driver (the paper's §5.5 case study):
trains GCN and AGNN on a synthetic power-law graph, every sparse matmul
running through Libra hybrid operators (forward SpMM/SDDMM, backward via
the transpose-plan SpMM + SDDMM duality).

    PYTHONPATH=src python examples/gnn_end2end.py --steps 60
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import gnn
from repro.sparse import power_law_csr


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--feat", type=int, default=64)
    ap.add_argument("--classes", type=int, default=8)
    args = ap.parse_args()

    a = power_law_csr(args.nodes, args.nodes, 10.0, seed=1)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.standard_normal((a.m, args.feat)).astype(np.float32))
    # planted community labels → learnable signal
    labels = jnp.asarray(rng.integers(0, args.classes, a.m))

    t0 = time.perf_counter()
    gops = gnn.GraphOps(a)
    print(f"preprocessed graph: nnz={a.nnz} "
          f"spmm_tc_ratio={gops.arrs.plan.tc.nblk} blocks "
          f"({time.perf_counter() - t0:.3f}s, reused every step)")

    norm = jnp.asarray(gnn.gcn_norm_edges(a))

    def ce(logits):
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, labels[:, None], 1).mean()

    for model_name, init, fwd, steps in (
        ("GCN", gnn.init_gcn, lambda p: gnn.gcn_forward(p, gops, feats, norm),
         args.steps),
        ("AGNN", gnn.init_agnn, lambda p: gnn.agnn_forward(p, gops, feats),
         max(args.steps // 3, 5)),
    ):
        params = init(jax.random.PRNGKey(0), [args.feat, 64, args.classes])
        vg = jax.jit(jax.value_and_grad(lambda p: ce(fwd(p))))
        t0 = time.perf_counter()
        first = last = None
        for s in range(steps):
            loss, g = vg(params)
            params = jax.tree.map(lambda p, gg: p - 0.3 * gg, params, g)
            first = first if first is not None else float(loss)
            last = float(loss)
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        acc = float((jnp.argmax(fwd(params), -1) == labels).mean())
        print(f"{model_name}: {steps} steps in {dt:.2f}s "
              f"loss {first:.3f}→{last:.3f} train_acc={acc:.2f}")
        assert last < first, "training must reduce the loss"
    print("gnn_end2end OK")


if __name__ == "__main__":
    main()
