"""Program spans: named host intervals in the profiler's own trace.

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation``.  While
a profiler trace runs (``jax.profiler.start_trace`` / ``stop_trace``, or
a profiler server that a client attaches to) the span lands on the host
plane of the ``.xplane.pb``, on the thread that opened it and on the same
timeline as the device's ops, with ``attrs`` as its stats.  With no
trace running it records nothing and costs about a microsecond.  The
profiler keeps the spans in memory and writes them at ``stop_trace``.

Attributes known only when the interval ends go on with
``set_metadata(**attrs)`` on the object the ``with`` statement yields.
A span's parent is the span it opened inside, on the same thread.

A span reads the host clock: never open one in code traced by
``jax.jit``, ``shard_map`` or ``pallas_call``, where it would fire once,
at trace time (``repro-lint`` reports it there as ``jax-host-time``).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPAN_NAMES", "span"]

#: every span the program emits (docs/ARCHITECTURE.md, "Spans").
SPAN_NAMES = (
    # serving front: launch/serve.py CohortServer
    "cohort.select", "cohort.snapshot", "cohort.swap", "cohort.inline_solve",
    "cohort.pools", "cohort.account", "cohort.observe", "cohort.update",
    "cohort.flush", "cohort.warm", "cohort.mailbox",
    # policy: the draw and the online TD step
    "policy.state", "policy.draw", "policy.q", "policy.observe",
    "policy.train",
    # streaming solver: streaming/solver.py BackgroundSolver
    "solver.task",
    # engine: cohort/engine.py CohortEngine
    "engine.prepare", "engine.fingerprint", "engine.sketch",
    "engine.upload", "engine.landmarks", "engine.kmeans", "engine.wait",
    "engine.publish",
)
_NAMES = frozenset(SPAN_NAMES)


def span(name: str, **attrs) -> TraceAnnotation:
    """A span named ``name`` (one of :data:`SPAN_NAMES`) with ``attrs``."""
    if name not in _NAMES:
        raise ValueError(f"span {name!r} is not in repro.obs.SPAN_NAMES")
    return TraceAnnotation(name, **attrs)
