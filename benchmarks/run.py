# One function per paper table. Prints ``name,us_per_call,derived`` CSV and
# optionally writes the same rows as machine-readable JSON (--json for one
# combined file, --json-dir for one BENCH_<suite>.json per suite) so the
# perf trajectory accumulates across PRs.
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (fig1,spmm,sddmm,"
                         "ablations,gnn,roofline,dist,serve,chaos,"
                         "reorder)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON: "
                         "[{name, us_per_call, derived}, ...]")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="also write one BENCH_<suite>.json per suite "
                         "(same row schema as --json)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="trace every suite and write one Perfetto/"
                         "Chrome-trace TRACE_<suite>.json per suite")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append this run's ratio bars (+ git sha/date) "
                         "to a BENCH_history.jsonl trajectory file")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_ablations,
        bench_chaos,
        bench_dist,
        bench_fig1_nnz1,
        bench_gnn_e2e,
        bench_reorder,
        bench_roofline,
        bench_sddmm,
        bench_serve,
        bench_spmm,
    )

    suites = {
        "fig1": bench_fig1_nnz1.run,
        "spmm": bench_spmm.run,
        "sddmm": bench_sddmm.run,
        "ablations": bench_ablations.run,
        "gnn": bench_gnn_e2e.run,
        "roofline": bench_roofline.run,
        "dist": bench_dist.run,
        "serve": bench_serve.run,
        "chaos": bench_chaos.run,
        "reorder": bench_reorder.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    unknown = only - set(suites)
    if unknown:
        ap.error(f"unknown suite(s): {sorted(unknown)} "
                 f"(choose from {sorted(suites)})")
    if args.json:  # fail fast on an unwritable path, not after the run
        # (append mode: must not truncate an existing trajectory file in
        # case the run is interrupted before the final dump)
        with open(args.json, "a"):
            pass
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = False
    records: list[dict] = []
    by_suite: dict[str, list[dict]] = {}
    for name, fn in suites.items():
        if name not in only:
            continue
        suite_records = by_suite.setdefault(name, [])
        tracer = None
        if args.trace_dir:
            from repro.obs.trace import Tracer, set_tracer

            tracer = Tracer()
            prev = set_tracer(tracer)
            root = tracer.span(f"suite.{name}").open()
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
                rec = {"name": row_name, "us_per_call": round(us, 1),
                       "derived": derived}
                records.append(rec)
                suite_records.append(rec)
        except Exception:
            failed = True
            print(f"{name},0.0,ERROR", flush=True)
            rec = {"name": name, "us_per_call": 0.0, "derived": "ERROR"}
            records.append(rec)
            suite_records.append(rec)
            traceback.print_exc()
        finally:
            if tracer is not None:
                root.close()
                set_tracer(prev)
                path = os.path.join(args.trace_dir,
                                    f"TRACE_{name}.json")
                with open(path, "w") as f:
                    json.dump(tracer.to_chrome_trace(), f)
                    f.write("\n")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
            f.write("\n")
    if args.json_dir:
        for suite, recs in by_suite.items():
            with open(os.path.join(args.json_dir,
                                   f"BENCH_{suite}.json"), "w") as f:
                json.dump(recs, f, indent=1)
                f.write("\n")
    if args.history:
        from benchmarks.history import append_records

        rec = append_records(args.history, records,
                             suites=sorted(by_suite))
        print(f"history: appended {rec['sha']} "
              f"({len(rec['bars'])} bars) to {args.history}",
              file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
