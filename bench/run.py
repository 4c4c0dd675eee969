#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload gcn.arxiv --seed 7 --seconds 10 --trace 0

Run from the root of a checkout, on a machine whose JAX finds a TPU with
as many chips as the cell asks for; anywhere else it exits non-zero and
prints no result. The last stdout line is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and ``checks`` last); earlier lines are JSON records
of what the run did. JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
(the program's ``repro.launch.compile_cache``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.models.gnn  # noqa: F401
    except ImportError as exc:
        print(f"bench: the program (src/repro) is not in this checkout: "
              f"{exc}", file=sys.stderr)
        return 2
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    benchmark = harness.load_json(ROOT / "BENCHMARK.json")
    chips = harness.cell_spec(args.workload, benchmark)["chips"]
    cache = enable_compile_cache()
    import jax

    t_import = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    harness.peaks_for(devices[0].device_kind)
    harness.log(stage="start-up", compile_cache=cache,
                import_s=t_import - T_START, devices_s=t_devices - t_import)
    cell = harness.load_cell(args.workload, benchmark)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
