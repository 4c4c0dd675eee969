#!/usr/bin/env python3
"""Bring-up smoke run of Libra's main path on one TPU chip.

Runs three phases in one process at ogbn-arxiv scale — a power-law
graph of 169,343 nodes and 1,166,243 edges generated from ``--seed`` to
the published statistics (Hu et al., "Open Graph Benchmark", 2020):

1. **operators** — ``LibraSpMM`` (n = 256) and ``LibraSDDMM`` (kf = 128)
   with ``backend="pallas"`` on the power-law graph (VPU-heavy) and on a
   block-structured matrix of the same size (MXU-heavy), each checked
   against a plain ``jax.numpy`` float32 COO reference on the device:
   exactly on small-integer data, within ``RAND_TOL`` on random data;
2. **training** — ``GraphOps(spec=ExecSpec(backend="pallas",
   tune="model"))`` through ``make_gcn_train_step`` (3-layer GCN, hidden
   256, 128 features, 40 classes — OGB's arxiv GCN baseline), then AGNN
   steps (SDDMM → edge softmax → SpMM, forward and backward). Losses
   must be finite and fall; the first loss must match the XLA reference
   path within ``LOSS_RTOL``;
3. **serving** — the graph in ``GraphRegistry(backend="pallas")``, a few
   SpMM and SDDMM requests through ``SparseEngine``; any degraded rung,
   failure or open breaker fails the run.

Every compiled Pallas apply must contain ``tpu_custom_call``, so no phase
is quietly interpreted, on the CPU or on the XLA reference.

    python3 chip_smoke.py                 # one chip, all phases
    python3 chip_smoke.py --four-chips    # DistGraphOps GCN on 4 chips
                                          # (replicated + rowshard) vs 1
    python3 chip_smoke.py --rehearse      # same phases, tiny, on the CPU
                                          # (Pallas interpreter)

Earlier stdout lines are JSON records, one per check. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; the exit code
is non-zero when a phase fails, and there is no result line when the
expected device is absent.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time
import traceback

#: Normalized max error allowed on random data: ``max|out - ref| /
#: max|ref|``. The MXU rounds float32 operands toward bfloat16 at the
#: default precision (relative error ~2^-9 per product).
RAND_TOL = 1e-2
#: Relative tolerance of the first training loss, Pallas vs XLA path.
LOSS_RTOL = 1e-2
#: SGD rates: large enough that each step's fall at full size (about
#: 1e-3 for GCN, 5e-3 for AGNN on the CPU) stands clear of the TPU's
#: reduced-precision float32 matmuls.
GCN_LR, AGNN_LR = 1.0, 0.2


@dataclasses.dataclass(frozen=True)
class Size:
    nodes: int
    edges: int
    n: int          # SpMM dense width
    kf: int         # SDDMM feature width
    feats: int
    hidden: int
    classes: int
    steps: int


# ogbn-arxiv: 169,343 nodes, 1,166,243 edges, 128 features, 40 classes.
FULL = Size(169_343, 1_166_243, n=256, kf=128, feats=128, hidden=256,
            classes=40, steps=5)
# Same average degree, interpreter-sized.
TINY = Size(512, 3_526, n=128, kf=128, feats=32, hidden=32, classes=8,
            steps=4)


def log(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _warm_seconds(fn, *args, reps: int = 3) -> float:
    return min(_timed(fn, *args)[1] for _ in range(reps))


def _check_kernels(text: str, on_tpu: bool, what: str) -> bool:
    """Compiled/lowered program text holds a Pallas TPU kernel."""
    found = "tpu_custom_call" in text
    if on_tpu and not found:
        raise AssertionError(f"{what}: no tpu_custom_call — not the "
                             f"compiled Pallas kernels")
    return found


def _apply_text(op) -> str:
    return "\n".join(exe.as_text() for exe in op._apply_cache.values())


def _norm_err(out, ref) -> float:
    import numpy as np

    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1.0))


# ----------------------------------------------------------- operators ---
def phase_operators(size: Size, graphs: dict, seed: int, on_tpu: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ExecSpec
    from repro.core.sddmm import LibraSDDMM
    from repro.core.spmm import LibraSpMM
    from repro.sparse.matrix import SparseCSR

    @jax.jit
    def coo_spmm(rows, cols, vals, b):
        # CSR order: rows are sorted.
        return jax.ops.segment_sum(vals[:, None] * b[cols], rows,
                                   num_segments=b.shape[0],
                                   indices_are_sorted=True)

    @jax.jit
    def coo_sddmm(rows, cols, x, y):
        return jnp.sum(x[rows] * y[cols], axis=1)

    rng = np.random.default_rng(seed)
    for name, a in graphs.items():
        # Small-integer values survive the MXU's reduced default
        # float32 precision, so the integer run must match exactly.
        ints = rng.integers(1, 4, a.nnz) * rng.choice([-1, 1], a.nnz)
        a_int = SparseCSR(a.m, a.k, a.indptr, a.indices,
                          ints.astype(np.float32))
        rows, cols, vals = (jnp.asarray(v) for v in a_int.to_coo())

        t0 = time.perf_counter()
        spmm = LibraSpMM(a_int, spec=ExecSpec(
            backend="pallas", tune="model", tune_n=size.n))
        t_plan = time.perf_counter() - t0
        b_int = jnp.asarray(rng.integers(-4, 5, (a.k, size.n)), jnp.float32)
        b_rand = jnp.asarray(rng.standard_normal((a.k, size.n)), jnp.float32)
        out, t_first = _timed(spmm, b_int)
        kernels = _check_kernels(_apply_text(spmm), on_tpu, f"{name} spmm")
        exact = bool(jnp.array_equal(out, coo_spmm(rows, cols, vals, b_int)))
        err = _norm_err(spmm(b_rand), coo_spmm(rows, cols, vals, b_rand))
        log("operators", op="spmm", graph=name, rows=a.m, nnz=a.nnz,
            n=size.n, tc_ratio=spmm.tc_ratio, plan_s=t_plan,
            first_call_s=t_first, warm_call_s=_warm_seconds(spmm, b_rand),
            tpu_custom_call=kernels, exact_int=exact, rand_err=err,
            rand_tol=RAND_TOL)
        assert exact and err <= RAND_TOL, (name, "spmm", exact, err)

        t0 = time.perf_counter()
        sddmm = LibraSDDMM(a_int, spec=ExecSpec(
            backend="pallas", tune="model", tune_kf=size.kf))
        t_plan = time.perf_counter() - t0
        x_int, y_int = (jnp.asarray(rng.integers(-2, 3, (a.m, size.kf)),
                                    jnp.float32) for _ in range(2))
        x_r, y_r = (jnp.asarray(rng.standard_normal((a.m, size.kf)),
                                jnp.float32) for _ in range(2))
        out, t_first = _timed(sddmm, x_int, y_int)
        kernels = _check_kernels(_apply_text(sddmm), on_tpu,
                                 f"{name} sddmm")
        exact = bool(jnp.array_equal(out, coo_sddmm(rows, cols, x_int,
                                                    y_int)))
        err = _norm_err(sddmm(x_r, y_r), coo_sddmm(rows, cols, x_r, y_r))
        log("operators", op="sddmm", graph=name, rows=a.m, nnz=a.nnz,
            kf=size.kf, tc_ratio=sddmm.tc_ratio, plan_s=t_plan,
            first_call_s=t_first,
            warm_call_s=_warm_seconds(sddmm, x_r, y_r),
            tpu_custom_call=kernels, exact_int=exact, rand_err=err,
            rand_tol=RAND_TOL)
        assert exact and err <= RAND_TOL, (name, "sddmm", exact, err)


# ------------------------------------------------------------ training ---
def _gcn_data(size: Size, a, seed: int):
    """Features, planted labels (argmax of a random projection, so the
    loss has something to fall toward), normalized edge values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import gnn

    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.standard_normal((a.m, size.feats)), jnp.float32)
    proj = rng.standard_normal((size.feats, size.classes))
    labels = jnp.asarray(np.argmax(np.asarray(feats) @ proj, axis=1))
    norm = jnp.asarray(gnn.gcn_norm_edges(a))
    params = gnn.init_gcn(jax.random.PRNGKey(seed),
                          [size.feats, size.hidden, size.hidden,
                           size.classes])
    return feats, labels, norm, params


def _run_steps(step, params, args, steps: int):
    import numpy as np

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, loss = step(params, *args)
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    ok = bool(np.isfinite(losses).all() and losses[-1] < losses[0])
    return losses, times, ok


def phase_training(size: Size, a, seed: int, on_tpu: bool):
    import jax

    from repro.api import ExecSpec
    from repro.dist.gnn import (agnn_loss, gcn_loss, make_agnn_train_step,
                                make_gcn_train_step)
    from repro.models import gnn

    t0 = time.perf_counter()
    g = gnn.GraphOps(a, spec=ExecSpec(backend="pallas", tune="model"))
    t_plan = time.perf_counter() - t0
    # The same plans through the XLA reference apply.
    g_xla = copy.copy(g)
    g_xla.backend = "xla"
    feats, labels, norm, params = _gcn_data(size, a, seed)

    step = make_gcn_train_step(g, lr=GCN_LR)
    kernels = _check_kernels(
        step.lower(params, feats, labels, norm).as_text(), on_tpu,
        "gcn step")
    losses, times, falls = _run_steps(step, params,
                                      (feats, labels, norm), size.steps)
    ref0 = float(jax.jit(gcn_loss, static_argnums=1)(
        params, g_xla, feats, labels, norm))
    match = abs(losses[0] - ref0) <= LOSS_RTOL * max(abs(ref0), 1.0)
    log("training", model="gcn", layers=3, hidden=size.hidden,
        nodes=a.m, edges=a.nnz, plan_s=t_plan, first_step_s=times[0],
        warm_step_s=min(times[1:]), losses=losses, xla_first_loss=ref0,
        tpu_custom_call=kernels, losses_fall=falls, first_loss_match=match)
    assert falls and match, ("gcn", losses, ref0)

    pa = gnn.init_agnn(jax.random.PRNGKey(seed + 1),
                       [size.feats, size.hidden, size.classes])
    astep = make_agnn_train_step(g, lr=AGNN_LR)
    kernels = _check_kernels(astep.lower(pa, feats, labels).as_text(),
                             on_tpu, "agnn step")
    losses, times, falls = _run_steps(astep, pa, (feats, labels),
                                      size.steps)
    ref0 = float(jax.jit(agnn_loss, static_argnums=1)(
        pa, g_xla, feats, labels))
    match = abs(losses[0] - ref0) <= LOSS_RTOL * max(abs(ref0), 1.0)
    log("training", model="agnn", layers=2, hidden=size.hidden,
        first_step_s=times[0], warm_step_s=min(times[1:]), losses=losses,
        xla_first_loss=ref0, tpu_custom_call=kernels, losses_fall=falls,
        first_loss_match=match)
    assert falls and match, ("agnn", losses, ref0)


# ------------------------------------------------------------- serving ---
def phase_serving(size: Size, a, seed: int, on_tpu: bool):
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import GraphRegistry, ServeError, SparseEngine

    rng = np.random.default_rng(seed)
    registry = GraphRegistry(backend="pallas", width_buckets=(size.n,),
                             panel_buckets=(1, 2))
    t0 = time.perf_counter()
    name = registry.register(a, name="arxiv")
    t_reg = time.perf_counter() - t0
    engine = SparseEngine(registry)
    rids = {}
    for width in (size.n, size.n // 2, size.n // 4):
        b = jnp.asarray(rng.standard_normal((a.k, width)), jnp.float32)
        rids[engine.submit(name, "spmm", b=b)] = ("spmm", width)
    for _ in range(2):
        x = jnp.asarray(rng.standard_normal((a.m, size.kf)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((a.k, size.kf)), jnp.float32)
        rids[engine.submit(name, "sddmm", x=x, y=y)] = ("sddmm", size.kf)
    t0 = time.perf_counter()
    results = engine.flush()
    t_flush = time.perf_counter() - t0
    bad = [r for r, v in results.items()
           if isinstance(v, ServeError) or not np.isfinite(v).all()]
    entry = registry.resolve(name)
    texts = [_apply_text(entry.op("spmm").op),
             "\n".join(e.as_text() for e in entry.op("sddmm")._cache.values())]
    kernels = all(_check_kernels(t, on_tpu, "serving") for t in texts)
    h = engine.health()
    open_breakers = {k: v["state"] for k, v in h["breakers"].items()
                     if v["state"] != "closed"}
    degraded = {k: v for k, v in h["degraded_served"].items() if v}
    failures = {k: v for k, v in h["failures"].items() if v}
    log("serving", register_s=t_reg, flush_s=t_flush, requests=len(rids),
        answered=len(results), bad=len(bad), tpu_custom_call=kernels,
        degraded=degraded, failures=failures, open_breakers=open_breakers,
        errors_returned=h["errors_returned"])
    assert (len(results) == len(rids) and not bad and not degraded
            and not failures and not open_breakers
            and not h["errors_returned"]), h


# ---------------------------------------------------------- four chips ---
def phase_four_chips(size: Size, a, seed: int, on_tpu: bool):
    """DistGraphOps GCN steps on a 4-device mesh (replicated and rowshard
    dense operand) against the same steps on one device (GraphOps)."""
    import jax
    import numpy as np

    from repro.api import ExecSpec
    from repro.dist import DistGraphOps, make_gcn_train_step
    from repro.models import gnn

    mesh = jax.make_mesh((4,), ("shards",), devices=jax.devices()[:4])
    spec = ExecSpec(backend="pallas", tune="model")
    t0 = time.perf_counter()
    g1 = gnn.GraphOps(a, spec=spec)
    gd = DistGraphOps(a, mesh, spec=spec)
    t_plan = time.perf_counter() - t0
    # Each device must hold its own shard of every partition's plan: shard
    # i on the mesh's i-th device (make_mesh orders devices by topology).
    devices = list(mesh.devices.flat)
    for part in (gd.part, gd.part_t, gd.part_sd):
        for key, arr in part.stacked.items():
            where = {s.device: s.index[0] for s in arr.addressable_shards}
            assert set(where) == set(devices), (key, where)
            for i, d in enumerate(devices):
                assert where[d] == slice(i, i + 1), (key, d, where[d])
    log("four_chips", check="plan_placement", shards=4, ok=True)

    feats, labels, norm, params = _gcn_data(size, a, seed)
    args = (feats, labels, norm)
    g_row = copy.copy(gd)       # same placed plans, row-sharded operand
    g_row.b_layout = "rowshard"
    runs = {}
    for layout, g in (("single", g1), ("replicated", gd),
                      ("rowshard", g_row)):
        step = make_gcn_train_step(g, lr=GCN_LR)
        kernels = _check_kernels(step.lower(params, *args).as_text(),
                                 on_tpu, f"gcn step {layout}")
        losses, times, falls = _run_steps(step, params, args, size.steps)
        runs[layout] = losses
        log("four_chips", layout=layout, devices=1 if g is g1 else 4,
            first_step_s=times[0], warm_step_s=min(times[1:]),
            losses=losses, losses_fall=falls, tpu_custom_call=kernels)
        assert falls, (layout, losses)
    base = np.asarray(runs["single"])
    gaps = {k: float(np.abs(np.asarray(v) - base).max() / max(base.max(), 1.0))
            for k, v in runs.items() if k != "single"}
    log("four_chips", check="loss_match", plan_s=t_plan, rel_gap=gaps,
        tol=LOSS_RTOL)
    assert all(v <= LOSS_RTOL for v in gaps.values()), gaps


# ---------------------------------------------------------------- main ---
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (Pallas interpreter)")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 4-device DistGraphOps GCN comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({exc})", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    devices = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    need = 4 if args.four_chips else 1
    if devices[0].platform != want or len(devices) < need:
        print(f"chip_smoke: need {need} {want} device(s), JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    on_tpu = want == "tpu"
    size = TINY if args.rehearse else FULL
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": need}
    log("start", size=dataclasses.asdict(size), seed=args.seed,
        device=device, jax=jax.__version__)

    from repro.sparse.generate import block_graph, power_law_graph

    t0 = time.perf_counter()
    arxiv = power_law_graph(size.nodes, size.edges, seed=args.seed)
    blocks = block_graph(size.nodes, size.edges, seed=args.seed + 1)
    log("graphs", seconds=time.perf_counter() - t0, arxiv_nnz=arxiv.nnz,
        block_nnz=blocks.nnz)

    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(
            size, arxiv, args.seed, on_tpu))]
    else:
        phases = [
            ("operators", lambda: phase_operators(
                size, {"arxiv_powerlaw": arxiv, "block": blocks},
                args.seed, on_tpu)),
            ("training", lambda: phase_training(size, arxiv, args.seed,
                                                on_tpu)),
            ("serving", lambda: phase_serving(size, arxiv, args.seed,
                                              on_tpu)),
        ]
    ok = True
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            passed = True
        except Exception:          # report the phase, run the rest
            traceback.print_exc()
            passed = False
        ok &= passed
        log(name, done=True, passed=passed,
            seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
