"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines; detailed records are written
to results/bench/*.json.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller trials/datasets (CI budget)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (e.g. kernels,engine)")
    ap.add_argument("--ci", action="store_true",
                    help="machine-readable drift gate: emit GitHub "
                         "::warning:: annotations for every drift beyond "
                         "tolerance (deterministic headlines 20%%; "
                         "wall-clock ratios 20%% full / 50%% quick tier, "
                         "advisory) and exit nonzero when a deterministic "
                         "headline drifts from the committed "
                         "benchmarks/BENCH_engine.json baseline")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_async, bench_engine, bench_kernels,
                            bench_losslessness, bench_regression,
                            bench_roofline, bench_scalability,
                            bench_secure_agg, bench_serve, bench_staleness)

    suites = {
        "losslessness": lambda: bench_losslessness.run(
            trials=1 if args.quick else 3,
            scale=0.25 if args.quick else 0.5,
            epochs=8 if args.quick else 12),
        "regression": lambda: bench_regression.run(
            trials=1 if args.quick else 3,
            scale=0.25 if args.quick else 0.5),
        "async": lambda: bench_async.run(
            epochs=3.0 if args.quick else 6.0),
        # thread-sim party sweep (paper Figs. 2/7) — renamed so the
        # engine's party-axis scaling suite can own "scalability"
        "async_scalability": lambda: bench_scalability.run(
            epochs=1.5 if args.quick else 3.0),
        "scalability": lambda: bench_engine.run_scalability(
            quick=args.quick),
        "staleness": lambda: bench_staleness.run(
            epochs=4 if args.quick else 8),
        "secure_agg": bench_secure_agg.run,
        "kernels": bench_kernels.run,
        "engine": lambda: bench_engine.run(quick=args.quick),
        "multi_dominator": lambda: bench_engine.run_multi_dominator(
            quick=args.quick),
        "pipelined": lambda: bench_engine.run_pipelined(quick=args.quick),
        "deep": lambda: bench_engine.run_deep(quick=args.quick),
        "deep_multi": lambda: bench_engine.run_deep_multi(
            quick=args.quick),
        "deep_pipelined": lambda: bench_engine.run_deep_pipelined(
            quick=args.quick),
        "faults": lambda: bench_engine.run_faults(quick=args.quick),
        "guards": lambda: bench_engine.run_guards(quick=args.quick),
        "serve": lambda: bench_serve.run(quick=args.quick),
        "roofline": bench_roofline.run,
    }
    if args.ci:
        bench_engine.set_ci_mode(True)
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= suites.keys():
        ap.error(f"unknown suite(s) {sorted(only - suites.keys())}; "
                 f"choose from {sorted(suites)}")
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only is not None and name not in only:
            continue
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print("FAILED SUITES:", failed, file=sys.stderr)
        raise SystemExit(1)
    if args.ci and bench_engine.gating_drifts():
        for e in bench_engine.gating_drifts():
            print(f"DRIFT GATE: {e['name']} {e['drift']:.0%} "
                  f"({e['fresh']:.2f} vs committed {e['committed']:.2f})",
                  file=sys.stderr)
        raise SystemExit(2)


if __name__ == "__main__":
    main()
