"""Fixture: host spans inside and around traced code (parsed, not run)."""
import jax
from jax.profiler import TraceAnnotation

from repro import obs


@jax.jit
def spans_in_trace(x):
    with obs.span("engine.kmeans"):              # jax-host-time
        y = x * 2
    with jax.profiler.TraceAnnotation("inner"):  # jax-host-time
        y = y + 1
    return _helper(y)


def _helper(y):
    # reachable from the jitted root above -> still traced code
    with TraceAnnotation("helper"):              # jax-host-time
        return y - 1


def host_path(x):
    # around the call into the jitted function: what spans are for
    with obs.span("engine.kmeans"):
        return spans_in_trace(x)
