"""One run of one benchmark cell: set-up, a timed window of training
steps, the comparison with the reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name under ``bench/``:

- ``configs/<config>.json``: the sizes as run; ``configs/<config>.py``
  beside it: the program's step for it, its reference, its FLOP count;
- ``traffic/<mix>.json``: the graph's parameters for ``graphgen``;
- ``limits/<workload>.json``: the limit of each compared number;
- ``metrics/<metric>.py``: one reader per per-layer metric.

This module knows none of those names.
"""
from __future__ import annotations

import collections
import gc
import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: Steps of the program that the reference follows.
CHECKED_STEPS = 3


def log(**kv) -> None:
    """One JSON record on its own stdout line (never the last one)."""
    print(json.dumps(kv), flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, benchmark: dict) -> dict:
    """The ``workloads`` entry named ``workload``."""
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_cell(workload: str, benchmark: dict) -> dict:
    """Cell entry, configuration sizes and module, traffic and limits."""
    cell = cell_spec(workload, benchmark)
    cfg = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "cfg": load_json(ROOT / cfg["file"]),
        "model": importlib.import_module(f"bench.configs.{cell['config']}"),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
        "per_layer": [m for m in benchmark["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def peaks_for(kind: str) -> dict:
    """The peaks row of ``device_kind``; an unknown kind is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def cell_graph(c: dict):
    """The graph the cell trains on: its traffic's graph, with self loops
    added where the configuration says so."""
    from bench import graphgen

    graph = graphgen.generate(c["traffic"])
    if c["cfg"].get("self_loops"):
        graph = graphgen.with_self_loops(graph)
    return graph


def key_for(seed: int):
    """A PRNG key that tells apart every whole-number seed, also those
    past 32 bits."""
    import jax

    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def make_inputs(model, dims: list[int], nodes: int, seed: int):
    """Features, planted labels (argmax of a random projection of the
    features, so the loss has something to fall toward) and the initial
    weights, on the device in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, kp, kw = jax.random.split(key, 3)
        feats = jax.random.normal(kx, (nodes, dims[0]), jnp.float32)
        proj = jax.random.normal(kp, (dims[0], dims[-1]), jnp.float32)
        labels = jnp.argmax(feats @ proj, axis=1).astype(jnp.int32)
        return feats, labels, model.init_params(kw, dims)

    return make(key_for(seed))


def plan_counts(ops) -> dict:
    """What the plans hold (an earlier output line, not a metric): the
    MXU stream's share of the non-zeros, its condensed blocks, and the
    VPU stream's segments times their width, per leg. Empty when the
    program's plans no longer look like this."""
    out = {}
    for leg, attr in (("spmm", "arrs"), ("spmm_t", "arrs_t"),
                      ("sddmm", "arrs_sd")):
        try:
            plan = getattr(ops, attr).plan
            vpu = plan.vpu.vals if leg != "sddmm" else plan.vpu.rows
            out[leg] = {"tc_ratio": plan.tc.nnz / max(plan.nnz, 1),
                        "mxu_blocks": int(plan.tc.cols.shape[0]),
                        "bk": int(plan.tc.cols.shape[1]),
                        "vpu_segments": int(vpu.shape[0]),
                        "cs": int(vpu.shape[1]),
                        "vpu_row_copies": int(vpu.shape[0] * vpu.shape[1])}
        except (AttributeError, IndexError, TypeError):
            continue
    return out


class JaxEvents:
    """Counts JAX's monitoring events and sums their durations (tracing,
    lowering, compiling, compilation-cache hits and misses) between
    :meth:`take` calls."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        self.counts[event] += 1

    def _duration(self, event: str, duration: float, **kw) -> None:
        self.counts[event] += 1
        self.seconds[event] += duration

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def take(self) -> dict:
        """What was heard since the last call, and forget it."""
        out = {"counts": dict(self.counts), "seconds": dict(self.seconds)}
        self.counts.clear()
        self.seconds.clear()
        return out


def run_window(step, params, seconds: float, annotate):
    """Steps back to back for about ``seconds``: each step is dispatched
    while the one before it runs, and the host waits only for the step
    before that, so the device always has the next step queued. Dispatch
    stops when the queued step would end past ``seconds``; the window
    ends when the last step's outputs are ready. At least
    ``CHECKED_STEPS - 1`` steps run. Returns the final parameters, the
    parameters after the window's ``CHECKED_STEPS - 1``-th step, the loss
    arrays and the window's length."""
    import jax

    losses, kept = [], None
    t0 = time.perf_counter()
    with annotate("bench.window"):
        prev = None
        while True:
            with annotate("bench.dispatch"):
                params, loss = step(params)
            losses.append(loss)
            if len(losses) == CHECKED_STEPS - 1:
                kept = params
            if prev is not None:
                with annotate("bench.wait"):
                    prev.block_until_ready()
            prev = loss
            now = time.perf_counter() - t0
            per_step = now / max(len(losses) - 1, 1)
            if (len(losses) >= CHECKED_STEPS - 1
                    and now + per_step >= seconds):
                break
        with annotate("bench.drain"):
            jax.block_until_ready((params, loss))
    return params, kept, losses, time.perf_counter() - t0


def device_info(devices, count: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": count}


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(c: dict, seed: int, seconds: float, trace: bool, t_start: float,
        devices) -> dict:
    """One run of the cell ``c`` (from :func:`load_cell`) on ``devices``,
    which the caller has checked; returns the result line's object."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check, tracing

    cell, cfg, model, traffic = c["cell"], c["cfg"], c["model"], c["traffic"]
    workload, chips = cell["name"], cell["chips"]
    device = device_info(devices, chips)
    log(stage="start", workload=workload, seed=seed, seconds=seconds,
        trace=trace, device=device, jax=jax.__version__)

    events = JaxEvents()
    log(stage="imports", since_start_s=time.perf_counter() - t_start)
    t = time.perf_counter()
    graph = cell_graph(c)
    log(stage="graph", family=traffic["family"],
        symmetric=bool(traffic.get("symmetric")),
        self_loops=bool(cfg.get("self_loops")), nodes=graph.nodes,
        edges=graph.edges, max_in_degree=int(np.diff(graph.indptr).max()),
        seconds=time.perf_counter() - t)

    dims = model.dims(cfg, traffic)
    t = time.perf_counter()
    feats, labels, p0 = make_inputs(model, dims, graph.nodes, seed)
    ev = model.edge_values(graph)
    inputs = {"feats": feats, "labels": labels}
    if ev is not None:
        inputs["edges"] = jnp.asarray(ev)
    jax.block_until_ready(inputs)
    log(stage="inputs", seconds=time.perf_counter() - t,
        jax_events=events.take())

    from repro.api import ExecSpec
    from repro.models.gnn import GraphOps
    from repro.obs.trace import Tracer, use_tracer
    from repro.sparse.matrix import SparseCSR

    a = SparseCSR(graph.nodes, graph.nodes, graph.indptr, graph.indices,
                  np.ones(graph.edges, np.float32))
    spans = Tracer(enabled=trace)
    t = time.perf_counter()
    with use_tracer(spans):
        ops = GraphOps(a, spec=ExecSpec(**cfg["spec"]))
    plan_build_s = time.perf_counter() - t
    span_s = collections.Counter()
    for root in spans.roots:
        span_s[root.name] += root.duration
    log(stage="plan", plan_build_s=plan_build_s, spans_s=dict(span_s),
        counts=plan_counts(ops))

    step = model.make_step(ops, cfg, inputs)
    events.take()
    t = time.perf_counter()
    p1, loss1 = jax.block_until_ready(step(p0))
    setup_s = time.perf_counter() - t_start
    log(stage="warm", setup_s=setup_s, first_step_s=time.perf_counter() - t,
        first_loss=float(loss1), jax_events=events.take())

    tracer = tracing.DeviceTrace(enabled=trace)
    with tracer:
        params, p3, window_losses, window_s = run_window(
            step, p1, seconds, tracer.annotate)
    in_window = events.take()
    events.close()
    steps = len(window_losses)
    step_s = window_s / steps
    mem_peak = memory_peak(devices[:chips])
    losses = [float(loss1)] + [float(x) for x in window_losses]
    failed = sum(not np.isfinite(x) for x in losses[1:])
    log(stage="window", steps=steps, window_s=window_s, step_s=step_s,
        compiles_in_window=in_window["counts"].get(
            JaxEvents.COMPILE, 0), losses=losses)

    prog = {"losses": losses[:CHECKED_STEPS],
            "p0": jax.device_get(p0), "p1": jax.device_get(p1),
            "p3": jax.device_get(p3)}
    del ops, step, params, p1, p3, window_losses
    gc.collect()
    ref_graph = {"rows": jnp.asarray(graph.rows()),
                 "cols": jnp.asarray(graph.indices), "nodes": graph.nodes}
    t = time.perf_counter()
    ref_step = check.reference_step(model, ref_graph, inputs, cfg["lr"])
    r_losses, r1, r3 = check.run_reference(ref_step, p0, CHECKED_STEPS)
    ref = {"losses": r_losses, "p0": prog["p0"],
           "p1": jax.device_get(r1), "p3": jax.device_get(r3)}
    numbers, leaves = check.gaps(cfg["lr"], prog, ref)
    correct, shown = check.verdict(numbers, c["limits"])
    log(stage="check", reference_s=time.perf_counter() - t,
        reference_losses=r_losses, program_losses=prog["losses"],
        leaves=leaves)

    result = {"correct": correct, "attempted": steps, "failed": failed}
    if trace:
        reduced = tracer.reduce(steps)
        metrics = {}
        ctx = {"plan_build_s": plan_build_s, "step_s": step_s,
               "flops_per_step": model.step_flops(graph.nodes, graph.edges,
                                                  dims),
               "peaks": peaks_for(device["kind"]), "chips": chips,
               "trace": reduced}
        for m in c["per_layer"]:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        metrics = {"step_s": {"value": step_s, "unit": "s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    device["memory_peak_bytes"] = mem_peak
    result.update(metrics=metrics, device=device, checks=shown)
    # The checks come last in the result line, and as the last lines of
    # standard error.
    for k, v in shown.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return result
