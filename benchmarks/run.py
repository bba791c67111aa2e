"""Benchmark registry — one module per paper table/figure + system perf.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).

  PYTHONPATH=src python -m benchmarks.run                # everything
  PYTHONPATH=src python -m benchmarks.run --only table2  # one suite
  REPRO_BENCH_SCALE=full ... --only table2               # paper-scale FL
  PYTHONPATH=src python -m benchmarks.run --suite realism --small --check

Suites:
  table2    — paper Table 2: rounds-to-accuracy per selection policy
  table3    — paper Table 3: evaluation criteria of DQRE-SCnet
  fig6      — paper Fig. 6: accuracy-vs-round curves
  kernels   — Pallas/jnp kernel micro-benchmarks
  serve     — concurrent cohort serving: serialized vs coalesced selects
  roofline  — §Roofline baseline table from the dry-run artifacts
  realism   — client-realism scenarios: policies under availability /
              straggler / dropout / churn chaos (emits BENCH_fed.json)
"""

from __future__ import annotations

import argparse
import sys
import time


SUITES = ["table2", "table3", "fig6", "kernels", "serve", "roofline",
          "realism"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", "--suite", dest="only", default=None,
                    help=f"comma-separated subset of {SUITES}")
    ap.add_argument("--small", action="store_true",
                    help="CI-sized realism suite (gated scenarios only)")
    ap.add_argument("--check", action="store_true",
                    help="fail if the realism suite's DQN-vs-stratified "
                         "gate does not hold")
    ap.add_argument("--max-rounds", type=int, default=None,
                    help="cap FL rounds per realism run (wiring smoke; "
                         "the gate expects the default budget)")
    args = ap.parse_args()
    selected = args.only.split(",") if args.only else SUITES
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    csv_rows: list = []
    t0 = time.time()
    for suite in selected:
        if suite == "table2":
            from benchmarks import table2_rounds
            table2_rounds.run(csv_rows)
        elif suite == "table3":
            from benchmarks import table3_metrics
            table3_metrics.run(csv_rows)
        elif suite == "fig6":
            from benchmarks import fig6_curves
            fig6_curves.run(csv_rows)
        elif suite == "kernels":
            from benchmarks import kernel_bench
            kernel_bench.run(csv_rows)
        elif suite == "serve":
            from benchmarks import serve_bench
            serve_bench.run(csv_rows)
        elif suite == "roofline":
            from benchmarks import roofline_table
            roofline_table.run(csv_rows)
        elif suite == "realism":
            from benchmarks import realism_bench
            summary = realism_bench.run(csv_rows, small=args.small,
                                        max_rounds=args.max_rounds)
            if args.check and realism_bench.check(summary):
                raise SystemExit(1)
        else:
            print(f"unknown suite {suite!r}", file=sys.stderr)
            raise SystemExit(2)

    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"# total {time.time()-t0:.1f}s, {len(csv_rows)} rows",
          file=sys.stderr)


if __name__ == "__main__":
    main()
