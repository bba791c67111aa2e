"""From a profiler trace (``.xplane.pb``) to the numbers readers need.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per operation run on the chip, named by its HLO text
(``%nystrom_gram_pallas.1 = f32[64,64] custom-call(...)``), and their
``XLA Modules`` line one per program (``jit_kmeans(<hash>)``).  An op is
known by the instruction name before `` = `` and its program's name
before ``(``.  What a name belongs to is data (``kernels.json``): a group
is matched by a substring of the op's or of its program's name.

- busy: the union of op intervals inside the traced window, per chip,
  averaged over the chips the cell uses;
- per group: device seconds, and how many times its ``count_by``
  substring ran (one per solve, for the Nyström passes);
- breakdown: the ten ops that took most time, and the ten longest idle
  gaps of chip 0, each named by the harness spans (``bench.select``,
  ``bench.observe``, ``bench.update``) the host was inside of.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional, Tuple

_KERNELS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernels.json")
SPANS = ("bench.select", "bench.observe", "bench.update")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over the cell's chips
    group_s: Dict[str, float]              # device seconds per group
    group_runs: Dict[str, int]             # runs of each group's counter
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    chips_seen: int = 0                    # device planes with ops


def name_table() -> dict:
    with open(_KERNELS) as f:
        return json.load(f)["groups"]


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def op_name(hlo: str) -> str:
    """``%fusion.53 = f32[...] fusion(...)`` -> ``fusion.53``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _modules(plane) -> List[Tuple[float, float, str]]:
    line = _line(plane, "XLA Modules")
    if line is None:
        return []
    return sorted((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(", 1)[0]) for e in line.events)


def _module_of(modules, t: float) -> str:
    # modules on one chip run one at a time: the last one started before t
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] >= t:
        return modules[lo - 1][2]
    return ""


def _host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name.split(".", 1)[1]))
    return spans


def _gap_label(s: float, e: float, spans) -> str:
    inside = sorted({name for a, b, name in spans if a < e and b > s})
    return "+".join(inside) if inside else "none"


def reduce(planes, window_ns: Tuple[float, float], chips: int,
           groups: dict) -> Summary:
    """Reduce planes (``ProfileData.planes``) over ``window_ns``."""
    lo, hi = window_ns
    devices = sorted((p for p in planes if p.name.startswith("/device:TPU:")
                      and _line(p, "XLA Ops") is not None),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    group_s = {g: 0.0 for g in groups}
    group_runs = {g: 0 for g in groups}
    op_s: Dict[str, float] = {}
    busy = []
    gaps: List[Tuple[str, float]] = []
    spans = _host_spans(planes)
    for i, plane in enumerate(devices):
        modules = _modules(plane)
        intervals = []
        for e in _line(plane, "XLA Ops").events:
            iv = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
            if iv is None:
                continue
            intervals.append(iv)
            secs = (iv[1] - iv[0]) * 1e-9
            module = _module_of(modules, e.start_ns)
            op = op_name(e.name)
            key = f"{module}/{op}" if module else op
            op_s[key] = op_s.get(key, 0.0) + secs
            for g, spec in groups.items():
                if any(p in op for p in spec.get("ops", ())) or any(
                        p in module for p in spec.get("modules", ())):
                    group_s[g] += secs / len(devices)
                    if spec.get("count_by") and spec["count_by"] in op \
                            and i == 0:
                        group_runs[g] += 1
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_gap_label(s, e, spans), (e - s) * 1e-9))
    top_ops = sorted(op_s.items(), key=lambda x: -x[1])[:10]
    top_gaps = sorted(gaps, key=lambda x: -x[1])[:10]
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / max(len(busy), 1),
                   group_s=group_s, group_runs=group_runs,
                   device_ops=top_ops, idle_gaps=top_gaps,
                   chips_seen=len(devices))


def load_planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def window_of(planes) -> Tuple[float, float]:
    """The span of every event in the trace, in ns."""
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        for line in plane.lines:
            for e in line.events:
                lo = min(lo, e.start_ns)
                hi = max(hi, e.start_ns + e.duration_ns)
    return lo, hi


def summarize(trace_dir: str, *, chips: int) -> Summary:
    """Reduce the trace a run recorded under ``trace_dir``."""
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = list(load_planes(path))
    return reduce(planes, window_of(planes), chips, name_table())
