"""Benchmark runner: one section per paper table/figure + framework planes.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shrinks sizes
(used by the test suite); full mode is the reported configuration.
``--metrics-out PATH`` additionally writes a JSON snapshot of the obs
metrics registry (section wall times, kernel-dispatch ledger) plus every
CSV row — the machine-readable sibling of the printed table, uploaded as
a CI artifact by the quick-bench job.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: writes,reads,queries,joins,serve,"
                         "antientropy,recovery,placement,clock,mixed,ckpt,"
                         "kernels,roofline,lint")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot + rows to PATH")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (bench_antientropy, bench_checkpoint, bench_clock,
                   bench_joins, bench_kernels, bench_lint, bench_mixed,
                   bench_placement, bench_queries, bench_reads,
                   bench_recovery, bench_serve, bench_writes, roofline)

    sections = {
        "writes": lambda: bench_writes.main(quick=args.quick),     # Tab1/Fig1-3
        "reads": lambda: bench_reads.main(quick=args.quick),       # Tab2/Fig4-5
        "queries": lambda: bench_queries.main(quick=args.quick),   # §4.4
        "joins": lambda: bench_joins.main(quick=args.quick),       # planner
        "serve": lambda: bench_serve.main(quick=args.quick),       # serve layer
        "antientropy":
            lambda: bench_antientropy.main(quick=args.quick),      # §6 / AE
        "recovery":
            lambda: bench_recovery.main(quick=args.quick),         # WAL replay
        "placement":
            lambda: bench_placement.main(quick=args.quick),        # ring gate
        "clock": lambda: bench_clock.main(quick=args.quick),       # interval gate
        "mixed": lambda: bench_mixed.main(quick=args.quick),       # Fig6
        "ckpt": lambda: bench_checkpoint.main(quick=args.quick),   # framework
        "kernels": lambda: bench_kernels.main(quick=args.quick),
        "roofline": roofline.main,                                  # from dry-run
        "lint": lambda: bench_lint.main(quick=args.quick),          # CI gate cost
    }
    only = set(args.only.split(",")) if args.only else set(sections)

    from repro.obs.metrics import MetricsRegistry, lift_dispatch_stats

    registry = MetricsRegistry()
    collected = []
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(row)
                collected.append(row)
        except Exception as e:  # keep the harness running
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        elapsed = time.perf_counter() - t0
        registry.gauge(f"bench.section_seconds.{name}").set(elapsed)
        print(f"# section {name} took {elapsed:.1f}s", file=sys.stderr)

    if args.metrics_out:
        lift_dispatch_stats(registry)  # process-wide kernel-launch ledger
        with open(args.metrics_out, "w") as fh:
            json.dump({"metrics": registry.snapshot(), "rows": collected},
                      fh, indent=1)
        print(f"# metrics snapshot -> {args.metrics_out}", file=sys.stderr)


if __name__ == "__main__":
    main()
