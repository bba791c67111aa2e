"""Benchmark of the cohort-selection service: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the chips of the machine it is started on and on nothing else:
without a TPU, or with fewer chips than the cell asks for, it exits 2
before any phase and prints no result.  JAX's persistent compilation
cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the
checkout.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
The same numbers end standard error.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

CACHE_DIR = os.path.join(_ROOT, ".jax_cache")
TRACE_DIR = os.path.join(_ROOT, ".bench_trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int):
    """The machine's TPU devices, or a reason there are none to use."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"needs a TPU; JAX found {devices[0].platform!r}"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, found {len(devices)}"
    return devices, None


def _number(x):
    return float(x) if isinstance(x, (int, float)) else x


def result_line(out: dict, cell, trace: bool) -> dict:
    """The contract's last line from ``harness.run_cell``'s output."""
    from bench import spec
    run = out["run"]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = out["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": out["device_count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": all(c.ok for c in out["checks"])
            and out["compared_solves"] > 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device,
            "compiles_in_window": out["compiles_in_window"]}
    if trace and run.trace is not None and run.trace.chips_seen:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps]}
    line["checks"] = {c.name: {"value": _number(c.value), "limit": c.limit}
                      for c in out["checks"]}
    return line


def report(line: dict, out: dict) -> None:
    run = out["run"]
    lags = [s["issue"] - s["ready"] for s in run.selects] + [
        u["issue"] - u["due"] for u in run.updates]
    print(f"generator lag: max {max(lags, default=0.0) * 1e3:.3f} ms over "
          f"{len(lags)} requests; {len(run.selects)} selects, "
          f"{len(run.updates)} updates, {sum(s['count'] for s in run.solves)}"
          f" solves; programs built in the window: "
          f"{out['compiles_in_window']}; {out['compared_solves']} solves "
          f"compared in {out['compare_s']:.1f}s", file=sys.stderr)
    for e in out["errors"][:10]:
        print(f"error: {e}", file=sys.stderr)
    for c in out["checks"]:
        mark = "ok" if c.ok else "FAIL"
        print(f"{c.name} {c.value!r} limit {c.limit!r} {mark}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import spec
    cell = spec.load_cell(args.workload)
    devices, why = tpu_devices(cell.chips)
    if devices is None:
        print(f"bench/run.py: {why}", file=sys.stderr)
        return 2
    enable_compile_cache()
    from bench import harness
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           process_start=PROCESS_START,
                           trace_dir=TRACE_DIR)
    out["device_count"] = len(devices)
    line = result_line(out, cell, bool(args.trace))
    report(line, out)
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
