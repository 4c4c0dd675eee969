#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<workload>.json`` are set
from, at the cell's own size on the chip. Not part of a benchmark run.

    python3 bench/calibrate.py --workload gcn.arxiv \\
        --program-seeds 21,22,23 --control-seeds 31,32,33

- For each program seed: one harness run of the cell with the shortest
  window (the program's three checked steps against the reference). Its
  three numbers are the sound readings; their largest is the lower one.
- For each control seed: the float32 reference's three steps compared
  with (a) the control, the same reference computed in bfloat16, and
  (b) planted faults in the reference put in the program's place: the
  mean over half of the nodes (``half_batch``), and every step's loss
  altered by 1% where it is produced (``altered_loss``). A state left
  unchanged reads 1 on the four leaf measures by their definition and
  needs no run.

Each reading is one JSON line on stdout; the last line holds, per
number, the largest sound reading and the smallest control and fault
readings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_readings(c: dict, seed: int) -> dict[str, dict]:
    """The control's and the planted faults' numbers for one seed."""
    import jax
    import jax.numpy as jnp

    from bench import check, harness

    cfg, model, traffic = c["cfg"], c["model"], c["traffic"]
    graph = harness.cell_graph(c)
    dims = model.dims(cfg, traffic)
    feats, labels, p0 = harness.make_inputs(model, dims, graph.nodes, seed)
    inputs = {"feats": feats, "labels": labels}
    ev = model.edge_values(graph)
    if ev is not None:
        inputs["edges"] = jnp.asarray(ev)
    ref_graph = {"rows": jnp.asarray(graph.rows()),
                 "cols": jnp.asarray(graph.indices), "nodes": graph.nodes}
    lr = cfg["lr"]

    def follow(step, alter=1.0):
        losses, p1, p3 = check.run_reference(step, p0,
                                             harness.CHECKED_STEPS)
        return {"losses": [x * alter for x in losses],
                "p0": jax.device_get(p0), "p1": jax.device_get(p1),
                "p3": jax.device_get(p3)}

    ref = follow(check.reference_step(model, ref_graph, inputs, lr))
    half = jnp.arange(graph.nodes) < graph.nodes // 2
    runs = {
        "control_bf16": follow(check.reference_step(
            model, ref_graph, inputs, lr, dtype=jnp.bfloat16)),
        "half_batch": follow(check.reference_step(
            model, ref_graph, inputs, lr, mask=half)),
        "altered_loss": follow(check.reference_step(
            model, ref_graph, inputs, lr), alter=1.01),
    }
    return {k: check.gaps(lr, v, ref)[0] for k, v in runs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    benchmark = harness.load_json(ROOT / "BENCHMARK.json")
    c = harness.load_cell(args.workload, benchmark)
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: readings are taken on the TPU", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.program_seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    lower, upper = {}, {}
    for seed in seeds:
        res = harness.run(c, seed, 0.0, False, time.perf_counter(), devices)
        numbers = {k: v["value"] for k, v in res["checks"].items()}
        harness.log(reading="program", seed=seed, numbers=numbers)
        for k, v in numbers.items():
            lower[k] = max(lower.get(k, 0.0), v)
    for seed in controls:
        for kind, numbers in control_readings(c, seed).items():
            harness.log(reading=kind, seed=seed, numbers=numbers)
            for k, v in numbers.items():
                upper.setdefault(kind, {})
                upper[kind][k] = min(upper[kind].get(k, float("inf")), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
