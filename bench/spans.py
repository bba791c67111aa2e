"""The program's own spans in a profiler trace, for the per-layer readers.

The service opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries (``repro.obs``; names and attributes in docs/ARCHITECTURE.md,
"Spans").  They land on the trace's host planes, one line per thread, on
the device ops' timeline, with their attributes as event stats.  What a
program span is, is data (``spans.json``): a name prefix, as
``kernels.json`` names kernels.

- ``program_spans``: each program span inside a window, with its thread,
  start, end, stats and parent (the span it opened inside, on the same
  thread);
- ``spans_of(run)``: a traced run's spans (``Summary.spans`` where the
  trace summary carries them, else read from the run's ``.xplane.pb``);
- ``freshness_parts`` and ``publish_fates``: an update's age split into
  solver queue, solve, mailbox wait and select tail, and whether each
  published solve was served or superseded;
- ``idle_gaps``: the longest idle gaps of chip 0, each named by the
  harness spans (as ``trace.reduce`` names them) and then by the
  innermost program span open on each host thread during the gap.

A trace with no program spans (a program that emits none) gives empty
lists, and every reader built on them returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, List, Optional, Tuple

from bench import trace

_PREFIXES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "spans.json")


@dataclasses.dataclass(eq=False)
class Span:
    name: str
    thread: Tuple[int, int]              # (plane, line): one host thread
    start: float                         # ns, the trace's timeline
    end: float
    stats: Dict[str, object]
    parent: Optional["Span"] = dataclasses.field(default=None, repr=False)
    children: List["Span"] = dataclasses.field(default_factory=list,
                                               repr=False)
    depth: int = 0

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def within(self, name: str) -> List["Span"]:
        """Descendants named ``name``, in start order."""
        out, stack = [], list(reversed(self.children))
        while stack:
            s = stack.pop()
            if s.name == name:
                out.append(s)
            stack.extend(reversed(s.children))
        return out


def prefixes() -> Tuple[str, ...]:
    with open(_PREFIXES) as f:
        return tuple(json.load(f)["prefixes"])


def program_spans(planes, window_ns=None, names=None) -> List[Span]:
    """Program spans on the host planes, nested per thread, by start."""
    names = tuple(names or prefixes())
    lo, hi = window_ns or (float("-inf"), float("inf"))
    out: List[Span] = []
    for p, plane in enumerate(planes):
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            mine = sorted(
                (Span(e.name, (p, i), e.start_ns, e.start_ns + e.duration_ns,
                      dict(getattr(e, "stats", None) or ()))
                 for e in line.events if e.name.startswith(names)
                 and lo <= e.start_ns and e.start_ns + e.duration_ns <= hi),
                key=lambda s: (s.start, -s.end))
            stack: List[Span] = []
            for s in mine:
                while stack and stack[-1].end < s.end:
                    stack.pop()
                if stack:
                    s.parent, s.depth = stack[-1], stack[-1].depth + 1
                    stack[-1].children.append(s)
                stack.append(s)
            out.extend(mine)
    return sorted(out, key=lambda s: (s.start, -s.end))


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> Tuple[Span, ...]:
    planes = list(trace.load_planes(path))
    return tuple(program_spans(planes, trace.window_of(planes)))


def spans_of(run) -> List[Span]:
    """The program spans of a traced run; empty for an untraced one."""
    if run.trace is None:
        return []
    spans = getattr(run.trace, "spans", None)
    if spans is not None:
        return list(spans)
    from bench.run import TRACE_DIR
    path = trace.find_xplane(TRACE_DIR)
    if path is None:
        return []
    return list(_load(path, os.stat(path).st_mtime_ns))


def named(spans: List[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def _first(spans: List[Span], pred, key) -> Optional[Span]:
    hits = [s for s in spans if pred(s)]
    return min(hits, key=key) if hits else None


def freshness_parts(spans: List[Span]) -> Tuple[List[dict], int]:
    """Each update's age, from its ``cohort.update`` span's end to the end
    of the first select serving its version, in four parts (seconds):

    - ``queue``: to the start of the first ``cohort.warm`` whose
      snapshot holds the update (``version`` at least the update's);
    - ``solve``: that warm, to the end of its ``cohort.mailbox`` publish;
    - ``mailbox``: to the end of the first ``cohort.swap`` that serves
      the update's version or a later one;
    - ``tail``: to the end of that swap's ``cohort.select``.

    Returns the parts of every update the window holds whole, and how
    many updates it cut off (a covering span outside the window).
    """
    warms = named(spans, "cohort.warm")
    swaps = [s for s in named(spans, "cohort.swap")
             if "served" in s.stats and s.parent is not None
             and s.parent.name == "cohort.select"]
    parts, cut = [], 0
    for u in named(spans, "cohort.update"):
        v = u.stats.get("version")
        if v is None:
            continue
        w = _first(warms, lambda s: s.stats.get("version", -1) >= v,
                   key=lambda s: s.start)
        mail = w.within("cohort.mailbox") if w is not None else []
        sw = _first(swaps, lambda s: s.stats["served"] >= v,
                    key=lambda s: s.end)
        if not mail or sw is None:
            cut += 1
            continue
        sel = sw.parent
        parts.append(dict(queue=(w.start - u.end) * 1e-9,
                          solve=(mail[0].end - w.start) * 1e-9,
                          mailbox=(sw.end - mail[0].end) * 1e-9,
                          tail=(sel.end - sw.end) * 1e-9,
                          age=(sel.end - u.end) * 1e-9))
    return parts, cut


def publish_fates(spans: List[Span]) -> Tuple[int, int, int]:
    """``(served, superseded, cut)`` over the window's ``cohort.mailbox``
    publishes: the first ``cohort.swap`` ending after a publish whose
    ``served`` reaches its version serves exactly that version (served)
    or a later one (superseded); with no such swap, the window cut it."""
    swaps = [s for s in named(spans, "cohort.swap") if "served" in s.stats]
    served = superseded = cut = 0
    for m in named(spans, "cohort.mailbox"):
        v = m.stats.get("version")
        if v is None:
            continue
        sw = _first(swaps, lambda s: s.end > m.end and s.stats["served"] >= v,
                    key=lambda s: s.end)
        if sw is None:
            cut += 1
        elif sw.stats["served"] == v:
            served += 1
        else:
            superseded += 1
    return served, superseded, cut


def _innermost(spans: List[Span], s: float, e: float) -> List[str]:
    """Per host thread, the deepest program span covering more than half
    of [s, e]; spans on one thread nest, so at most one per depth can."""
    best: Dict[Tuple[int, int], Span] = {}
    for sp in spans:
        if min(sp.end, e) - max(sp.start, s) > (e - s) / 2:
            if sp.thread not in best or sp.depth > best[sp.thread].depth:
                best[sp.thread] = sp
    return sorted({sp.name for sp in best.values()})


def idle_gaps(planes, window_ns, spans: List[Span],
              top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps of chip 0 inside ``window_ns``, each
    labelled ``<harness spans> / <program spans>`` (just the harness
    label where no program span covers the gap)."""
    lo, hi = window_ns
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and trace._line(p, "XLA Ops") is not None]
    if not devices:
        return []
    chip0 = min(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    busy = trace._union([iv for e in trace._line(chip0, "XLA Ops").events
                         if (iv := trace._clip(e.start_ns,
                                               e.start_ns + e.duration_ns,
                                               lo, hi)) is not None])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                  key=lambda g: g[0] - g[1])[:top]
    harness = trace._host_spans(planes)
    out = []
    for s, e in gaps:
        label = trace._gap_label(s, e, harness)
        inner = _innermost(spans, s, e)
        out.append((f"{label} / {', '.join(inner)}" if inner else label,
                    (e - s) * 1e-9))
    return out
