"""Readings the benchmark's fixed numbers were set from, on the chip.

    python3 bench/tune.py sweep --workload <cell> --rates 0.3,0.5 --seconds 20
    python3 bench/tune.py limits --workload <cell> --seeds 1,2,3 --seconds 8
    python3 bench/tune.py limits --workload <cell> --seeds 1,2,3 --variant bf16
    python3 bench/tune.py limits --workload <cell> --seeds 1,2,3 --fault wrong_kmeans

``sweep`` runs the cell's window once per select rate, in one process,
and prints per rate the select latency, the generator's lag and the
rounds served per second: the knee is the highest rate whose lag does
not grow through the window.  ``limits`` runs short windows at the
cell's own load, one per seed, and prints the program's compared
numbers and checks, and whether the run came out correct.
``--variant bf16`` runs the program's own lower-precision path (bfloat16
affinity tiles), the control; ``--fault`` plants one of
``bench/faults.py``'s faults.  Each prints one JSON object per run, and
writes them to ``--out`` as JSON lines.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def _emit(record: dict, out) -> None:
    text = json.dumps(record, default=float)
    print(text, flush=True)
    if out is not None:
        out.write(text + "\n")
        out.flush()


def sweep(cell, rates, seconds: float, seed: int, out) -> None:
    import numpy as np

    from bench import harness
    for rate in rates:
        c = copy.copy(cell)
        c.cell = dict(cell.cell, select_rate_per_s=rate)
        res = harness.run_cell(c, seed, seconds, False,
                               process_start=time.perf_counter())
        sel = res["run"].selects
        lat = [s["done"] - s["due"] for s in sel]
        lag = [s["issue"] - s["due"] for s in sel]
        half = len(lag) // 2
        _emit(dict(rate=rate, selects=len(sel),
                   select_p50_ms=1e3 * float(np.median(lat)) if lat else None,
                   lag_first_half_ms=1e3 * float(np.mean(lag[:half] or [0])),
                   lag_second_half_ms=1e3 * float(np.mean(lag[half:] or [0])),
                   rounds_per_s=len(sel) / res["run"].close_s,
                   solves=sum(s["count"] for s in res["run"].solves),
                   round_s_mean=float(np.mean(
                       [s["done"] - s["issue"] for s in sel])
                       + np.mean(res["run"].observes or [0])),
                   failed=res["failed"]), out)


def limits(cell, seeds, seconds: float, variant: str, fault: str,
           out) -> None:
    from bench import faults, harness
    if fault:
        faults.FAULTS[fault](setattr)
    if variant != "f32":
        cell = copy.copy(cell)
        cell.config = dict(cell.config, engine=dict(
            cell.config.get("engine", {}), affinity_dtype=variant))
    for seed in seeds:
        res = harness.run_cell(cell, seed, seconds, False,
                               process_start=time.perf_counter())
        _emit(dict(seed=seed, variant=variant, fault=fault,
                   correct=all(c.ok for c in res["checks"]),
                   program=res["solve_numbers"],
                   checks={c.name: c.value for c in res["checks"]},
                   failed=res["failed"], errors=res["errors"][:3],
                   setup_s=res["run"].setup_s, compare_s=res["compare_s"],
                   memory_peak_bytes=res["memory_peak_bytes"]), out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("mode", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--variant", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import run, spec
    cell = spec.load_cell(args.workload)
    devices, why = run.tpu_devices(cell.chips)
    if devices is None:
        print(f"bench/tune.py: {why}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out = None
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        out = open(args.out, "a")
    try:
        if args.mode == "sweep":
            sweep(cell, [float(r) for r in args.rates.split(",")],
                  args.seconds, seeds[0], out)
        else:
            limits(cell, seeds, args.seconds, args.variant, args.fault, out)
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
