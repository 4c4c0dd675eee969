#!/usr/bin/env python3
"""Operator scopes in the device trace, and the plan build's spans.

The program runs each sparse operator of a training step under one JAX
named scope (``spmm``, ``sddmm``, ``edge_softmax``) and splits its
applies further (``revalue``, ``mxu``, ``vpu``, ``combine``); its plan
build opens ``graphops.*`` spans, and the ``preprocess.*`` spans of an
enabled tracer carry each plan's stream counts. This module reads both,
beside :mod:`bench.tracing`, whose numbers it leaves as they are:

- :func:`extract` is :func:`bench.tracing.extract` plus each device op's
  HLO ``op_name`` (looked up in the step's compiled text: a TPU
  profile's op events carry none) and the program's spans on the
  profiler's host plane;
- :func:`reduce` gives each operator scope's busy time per step, the
  union of its ops' intervals inside ``bench.window``, per device and
  averaged as ``busy_s`` is;
- :func:`readings` turns that, the plan build's span tree and the step's
  sparse calls (:data:`STEP_SPARSE_CALLS`) into per-operator device
  time, roofline shares and plan counters; :func:`checks` holds them to
  the trace's own totals.

``bench/run.py`` does not call it. As a probe of one cell (a plan build
under an enabled tracer, a warm-up step and a traced window, no
reference check):

    python3 bench/scopes.py --workload gcn.arxiv --seed 7 --seconds 10

prints the ``plan`` line's span tree, the ``scopes`` line (ms per step
by scope) and the ``readings`` and ``checks`` lines.
"""
from __future__ import annotations

import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __package__ in (None, ""):
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracing  # noqa: E402

#: Scopes whose busy time the readings use: an op is in ``a/b`` when its
#: scope path holds ``a`` and, after it, ``b``.
SCOPES = ("spmm", "spmm/vpu", "sddmm", "edge_softmax")

#: The ``scopes`` line: each op counts once, under the entry of the first
#: of these scopes it is in, or under ``other`` (dense layers,
#: normalisation, loss, SGD).
LINE = (("spmm/revalue", "spmm/revalue"), ("spmm/mxu", "spmm/mxu"),
        ("spmm/vpu", "spmm/vpu"), ("spmm/combine", "spmm/combine"),
        ("spmm", "spmm/other"), ("sddmm/mxu", "sddmm/mxu"),
        ("sddmm/vpu", "sddmm/vpu"), ("sddmm/combine", "sddmm/combine"),
        ("sddmm", "sddmm/other"), ("edge_softmax", "edge_softmax"))

#: First name component of the program's spans (``repro.obs.trace``).
PROGRAM_SPANS = ("graphops", "preprocess", "tune", "kernels", "serve",
                 "obs")

_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_path(op_name: str) -> tuple[str, ...]:
    """The scope names of an HLO ``op_name``, JAX's transform wrappers
    taken off each component: ``jit(f)/transpose(jvp(spmm))/vpu/x`` →
    ``("f", "spmm", "vpu", "x")``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part:
            out.append(part)
    return tuple(out)


def in_scope(path: tuple[str, ...], scope: str) -> bool:
    """Whether ``scope``'s components appear in ``path`` in order."""
    rest = iter(path)
    return all(part in rest for part in scope.split("/"))


def line_key(path: tuple[str, ...]) -> str:
    """The ``scopes`` line's entry for an op."""
    return next((key for scope, key in LINE if in_scope(path, scope)),
                "other")


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name → ``op_name`` from a compiled module's text. A
    TPU profile's op events name the HLO instruction but carry no
    ``op_name`` of their own, so the scopes come from the step's
    compiled text."""
    out = {}
    for line in hlo_text.splitlines():
        name, sep, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        m = _OP_NAME.search(rest) if sep else None
        if m:
            out[name.lstrip("%")] = m.group(1)
    return out


def extract(xplane_path: str, op_names: dict[str, str]) -> dict:
    """:func:`bench.tracing.extract`'s trace, with ``op_names`` (per
    device, one ``op_name`` per op, in the order of its ops, looked up in
    :func:`hlo_op_names`'s map; ``""`` for ops the compiler added, such
    as async copies) and ``spans`` (the program's spans on the host
    plane: name, start and duration in ns)."""
    from jax.profiler import ProfileData

    trace = tracing.extract(xplane_path)
    names, spans = {}, []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:TPU:"):
            names[plane.name] = [
                op_names.get(ev.name.partition(" = ")[0].lstrip("%"), "")
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            spans += [[ev.name, ev.start_ns, ev.duration_ns]
                      for line in plane.lines for ev in line.events
                      if ev.name.split(".", 1)[0] in PROGRAM_SPANS]
    trace.update(op_names=names, spans=spans)
    return trace


def reduce(trace: dict, steps: int) -> dict:
    """Milliseconds per step, averaged over the traced devices: busy time
    under each of :data:`SCOPES` (``scope_ms``), the ``scopes`` line
    (``line_ms``), and the Pallas kernels' busy time inside the ``spmm``
    and ``sddmm`` scopes (``pallas_scoped_ms``); ``unnamed`` counts the
    ops that had no ``op_name`` (those the compiler added), which the
    line counts under ``other``."""
    w_lo, w_hi = next((s, s + d) for n, s, d in trace["host"]
                      if n == "bench.window")
    scope_ns = {s: 0.0 for s in SCOPES}
    line_ns = {key: 0.0 for _, key in LINE + (("", "other"),)}
    pallas_ns, unnamed = 0.0, 0
    for dev, ops in trace["devices"].items():
        paths = [scope_path(n) for n in trace["op_names"][dev]]
        keys = [line_key(p) for p in paths]
        unnamed += sum(not p for p in paths)

        def busy(keep):
            return tracing._length(tracing._union(tracing._clip(
                [(op[1], op[1] + op[2]) for i, op in enumerate(ops)
                 if keep(i)], w_lo, w_hi)))

        for s in SCOPES:
            scope_ns[s] += busy(lambda i, s=s: in_scope(paths[i], s))
        for k in line_ns:
            line_ns[k] += busy(lambda i, k=k: keys[i] == k)
        pallas_ns += busy(lambda i: ops[i][3]
                          and keys[i].startswith(("spmm/", "sddmm/")))
    per_step = 1e-6 / max(len(trace["devices"]), 1) / steps
    return {"scope_ms": {k: v * per_step for k, v in scope_ns.items()},
            "line_ms": {k: v * per_step for k, v in line_ns.items()},
            "pallas_scoped_ms": pallas_ns * per_step, "unnamed": unnamed}


# ------------------------------------------------- sparse calls per step ---
def gcn_calls(nodes: int, edges: int, dims: list[int]) -> list[tuple]:
    """GCN, per layer: the forward SpMM by ``Â`` and the backward one by
    ``Âᵀ``, both at the layer's output width."""
    return [(op, edges, nodes, nodes, width) for width in dims[1:]
            for op in ("spmm", "spmm")]


def agnn_calls(nodes: int, edges: int, dims: list[int]) -> list[tuple]:
    """AGNN, per layer at its input width: the forward SDDMM and SpMM,
    the backward SDDMM ``dP``, and, except in the first layer, the three
    SpMMs of the input gradient (as :func:`bench.counts.agnn_step`)."""
    calls = []
    for i, width in enumerate(dims[:-1]):
        calls += [("sddmm", edges, nodes, nodes, width),
                  ("spmm", edges, nodes, nodes, width),
                  ("sddmm", edges, nodes, nodes, width)]
        if i > 0:
            calls += [("spmm", edges, nodes, nodes, width)] * 3
    return calls


#: Each configuration's sparse calls per step, ``(op, nnz, m, k, width)``,
#: from the layer equations alone and never from a plan.
STEP_SPARSE_CALLS = {"gcn": gcn_calls, "agnn": agnn_calls}


def call_bytes(op: str, nnz: int, m: int, k: int, width: int) -> int:
    """Compulsory HBM bytes of one call in float32: the sparse matrix's
    values, column ids and row pointers once, each dense operand once,
    the output once (SDDMM's output is one value per non-zero)."""
    if op == "spmm":
        return 4 * (2 * nnz + m + 1 + k * width + m * width)
    return 4 * (2 * nnz + m + 1 + m * width + k * width + nnz)


def least_seconds(calls, op: str, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``op``'s calls, each at the
    larger of its FLOPs over the peak and its bytes over HBM bandwidth,
    and which bound binds most of it."""
    flops_s = bytes_s = total = 0.0
    for c in calls:
        if c[0] != op:
            continue
        f = 2 * c[1] * c[4] / peaks["flops_per_s"]
        b = call_bytes(*c) / peaks["hbm_bytes_per_s"]
        flops_s, bytes_s, total = flops_s + f, bytes_s + b, total + max(f, b)
    return total, ("memory" if bytes_s >= flops_s else "compute")


# --------------------------------------------------------- plan spans ---
def _walk(tree: list[dict]):
    for node in tree:
        yield node
        yield from _walk(node["children"])


def build_report(tree: list[dict]) -> dict | None:
    """The ``graphops.build`` span: its seconds, its children's, and its
    self time (what no child covers)."""
    root = next((n for n in tree if n["name"] == "graphops.build"), None)
    if root is None:
        return None
    kids = [[c["name"] + (f"[{c['attrs']['leg']}]" if "leg" in c["attrs"]
                          else ""), c["dur_s"]] for c in root["children"]]
    return {"build_s": root["dur_s"], "children_s": kids,
            "self_s": root["dur_s"] - sum(s for _, s in kids)}


def vpu_slot_fill(tree: list[dict]) -> float | None:
    """Real VPU elements over VPU slots, in %, over the build's
    ``preprocess.spmm`` spans."""
    legs = [n["attrs"] for n in _walk(tree)
            if n["name"] == "preprocess.spmm" and "vpu_slots" in n["attrs"]]
    slots = sum(a["vpu_slots"] for a in legs)
    return 100.0 * sum(a["vpu_nnz"] for a in legs) / slots if slots else None


def plan_sddmm_s(tree: list[dict]) -> float | None:
    """Seconds of the SDDMM leg of the plan build (0.0 when the build
    made none)."""
    if build_report(tree) is None:
        return None
    return next((n["dur_s"] for n in _walk(tree)
                 if n["name"] == "graphops.leg"
                 and n["attrs"].get("leg") == "sddmm"), 0.0)


# ------------------------------------------------------------ readings ---
def readings(scoped: dict, tree: list[dict], calls: list[tuple],
             peaks: dict) -> dict:
    """The eight per-layer numbers of one traced run; a device-trace one
    is left out where its scope has no ops in the trace."""
    ms = scoped["scope_ms"]
    out = {}
    for name, scope in (("spmm_ms_per_step", "spmm"),
                        ("spmm_vpu_ms_per_step", "spmm/vpu"),
                        ("sddmm_ms_per_step", "sddmm"),
                        ("softmax_ms_per_step", "edge_softmax")):
        if ms[scope] > 0.0:
            out[name] = ms[scope]
    for op in ("spmm", "sddmm"):
        least, bound = least_seconds(calls, op, peaks)
        if ms[op] > 0.0 and least > 0.0:
            out[f"{op}_roofline"] = 100.0 * least / (ms[op] * 1e-3)
            out[f"{op}_roofline_bound"] = bound
    for name, fn in (("vpu_slot_fill", vpu_slot_fill),
                     ("plan_sddmm_s", plan_sddmm_s)):
        value = fn(tree)
        if value is not None:
            out[name] = value
    return out


def checks(scoped: dict, reduced: dict, read: dict) -> dict:
    """The scopes held to the trace's totals (:func:`bench.tracing.reduce`
    output): every kernel scoped, scopes inside busy time, the ``scopes``
    line summing to it, roofline shares in (0, 100]."""
    steps = reduced["steps"]
    busy = 1e3 * reduced["busy_s"] / steps
    pallas = 1e3 * reduced["pallas_s"] / steps
    ms = scoped["scope_ms"]
    line = sum(scoped["line_ms"].values())
    return {
        "pallas_scoped": abs(scoped["pallas_scoped_ms"] - pallas)
        <= 0.01 * pallas,
        "scopes_in_busy": ms["spmm"] + ms["sddmm"] + ms["edge_softmax"]
        <= busy * 1.0001 and ms["spmm/vpu"] <= ms["spmm"],
        "line_sums_to_busy": abs(line - busy) <= 0.01 * busy,
        "rooflines_in_range": all(0.0 < read[k] <= 100.0 for k in read
                                  if k.endswith("_roofline")),
        "busy_ms": busy, "pallas_ms": pallas, "line_ms_sum": line,
    }


# --------------------------------------------------------------- probe ---
def cut(trace: dict, keep: int) -> dict:
    """The first ``keep`` ops of the window on the first device, with the
    window shrunk to them and the host annotations clipped to it: a small
    recorded trace for the tests."""
    w_lo, w_hi = next((s, s + d) for n, s, d in trace["host"]
                      if n == "bench.window")
    dev = sorted(trace["devices"])[0]
    both = sorted(((op, name) for op, name in zip(trace["devices"][dev],
                                                   trace["op_names"][dev])
                   if op[1] >= w_lo and op[1] + op[2] <= w_hi),
                  key=lambda x: x[0][1])[:keep]
    lo, hi = both[0][0][1], max(op[1] + op[2] for op, _ in both)
    host = [["bench.window", lo, hi - lo]] + [
        [n, max(s, lo), min(s + d, hi) - max(s, lo)]
        for n, s, d in trace["host"] + trace["spans"]
        if n != "bench.window" and min(s + d, hi) > max(s, lo)]
    return {"devices": {dev: [op for op, _ in both]},
            "op_names": {dev: [name for _, name in both]},
            "host": [h for h in host if h[0].startswith("bench.")],
            "spans": [h for h in host if not h[0].startswith("bench.")]}


def _step_hlo(ops, cfg: dict, inputs: dict, params) -> str:
    """The compiled text of the program's jitted step for ``cfg`` (the
    same program the window ran, so the same instruction names)."""
    from repro.dist import gnn

    make = getattr(gnn, f"make_{cfg['model']}_train_step")
    args = [inputs[k] for k in ("feats", "labels", "edges") if k in inputs]
    return make(ops, lr=cfg["lr"]).lower(params, *args).compile().as_text()


def probe_cell(c: dict, seed: int, seconds: float, peaks: dict,
               save: str | None = None, keep: int = 300) -> dict:
    """One traced probe of the cell ``c`` (:func:`bench.harness.load_cell`)
    on the default device; returns the records it logged, by stage."""
    import collections
    import json
    import os
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import harness
    from repro.api import ExecSpec
    from repro.models.gnn import GraphOps
    from repro.obs.trace import Tracer, use_tracer
    from repro.sparse.matrix import SparseCSR

    cfg, model, traffic = c["cfg"], c["model"], c["traffic"]
    graph = harness.cell_graph(c)
    dims = model.dims(cfg, traffic)
    feats, labels, p0 = harness.make_inputs(model, dims, graph.nodes, seed)
    inputs = {"feats": feats, "labels": labels}
    ev = model.edge_values(graph)
    if ev is not None:
        inputs["edges"] = jnp.asarray(ev)
    a = SparseCSR(graph.nodes, graph.nodes, graph.indptr, graph.indices,
                  np.ones(graph.edges, np.float32))
    spans = Tracer()
    t = time.perf_counter()
    with use_tracer(spans):
        ops = GraphOps(a, spec=ExecSpec(**cfg["spec"]))
    plan_build_s = time.perf_counter() - t
    tree = spans.to_dict()
    roots = collections.Counter()
    for n in tree:
        roots[n["name"]] += n["dur_s"]
    out = {"plan": {"plan_build_s": plan_build_s, "roots_s": dict(roots),
                    "build": build_report(tree)}}
    step = model.make_step(ops, cfg, inputs)
    p1, _ = jax.block_until_ready(step(p0))
    tracer = tracing.DeviceTrace(enabled=True)
    with tracer:
        _, _, losses, window_s = harness.run_window(step, p1, seconds,
                                                    tracer.annotate)
    steps = len(losses)
    try:
        (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tracer.dir)
                   for f in fs if f.endswith(".xplane.pb")]
        trace = extract(path, hlo_op_names(_step_hlo(ops, cfg, inputs, p1)))
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    if save:
        with open(save, "w") as f:
            json.dump(cut(trace, keep), f)
    reduced = tracing.reduce(trace, steps)
    scoped = reduce(trace, steps)
    calls = STEP_SPARSE_CALLS[c["cell"]["config"]](graph.nodes, graph.edges,
                                                   dims)
    read = readings(scoped, tree, calls, peaks)
    out["window"] = {"steps": steps, "step_s": window_s / steps,
                     "busy_s": reduced["busy_s"],
                     "pallas_s": reduced["pallas_s"],
                     "ops": sum(map(len, trace["devices"].values())),
                     "unnamed_ops": scoped["unnamed"],
                     "program_spans_in_window": len(trace["spans"])}
    out["scopes"] = {"ms_per_step": scoped["line_ms"]}
    out["readings"] = read
    out["checks"] = checks(scoped, reduced, read)
    for stage, record in out.items():
        harness.log(stage=stage, **record)
    return out


def probe(workload: str, seed: int, seconds: float, save: str | None = None,
          keep: int = 300) -> int:
    """:func:`probe_cell` of ``workload`` on a TPU; exits non-zero
    anywhere else."""
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    benchmark = harness.load_json(harness.ROOT / "BENCHMARK.json")
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/scopes.py: {workload} needs a TPU; JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 3
    probe_cell(harness.load_cell(workload, benchmark), seed, seconds,
               harness.peaks_for(devices[0].device_kind), save, keep)
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--save", help="write the first --keep ops of the "
                    "window, scoped, to this JSON file")
    ap.add_argument("--keep", type=int, default=300)
    args = ap.parse_args(argv)
    return probe(args.workload, args.seed, args.seconds, args.save,
                 args.keep)


if __name__ == "__main__":
    sys.exit(main())
