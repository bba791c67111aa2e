"""The least time of a Nyström solve on a chip, from its sizes alone.

The work is counted once per solve, whatever implements it: a pipeline
that recomputes the affinity tile three times does the same useful work
as one that keeps it, so it reads as a lower share, never above 100 %.

Operations (multiply and add count two):

- the (N, m) cross-affinity, once: N·m·(2d + 3) (the distance dot, two
  norm adds and the scale; ``exp`` has no published peak and is left
  out), and the (m, m) landmark block: m·m·(2d + 3);
- SᵀS: 2·N·m²;
- the extension S·P: 2·N·m·k.

Bytes: the (N, d) rows read once, the (N, k) embedding written once, and
the m-sized operands (landmarks, W⁻¹ᐟ², projector, degrees) once.

A float32 dot at full precision is bounded by the bfloat16 peak, an int8
tile by the int8 peak.  Peaks come from ``peaks.json``, keyed by the
``device_kind`` JAX reports; an unknown kind is an error.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def nystrom_flops(n: int, m: int, d: int, k: int) -> float:
    affinity = (n + m) * m * (2 * d + 3)
    return float(affinity + 2 * n * m * m + 2 * n * m * k)


def nystrom_bytes(n: int, m: int, d: int, k: int) -> float:
    return 4.0 * (n * d + n * k + m * d + m * m + m * k + m)


def nystrom_least_s(n: int, m: int, d: int, k: int, device_kind: str,
                    affinity_dtype: str = "f32") -> tuple:
    """(least seconds, "compute" | "memory") for one solve's passes."""
    p = peaks(device_kind)
    peak = (p["int8_ops_per_s"] if affinity_dtype == "int8"
            else p["bf16_flops_per_s"])
    compute = nystrom_flops(n, m, d, k) / peak
    memory = nystrom_bytes(n, m, d, k) / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
