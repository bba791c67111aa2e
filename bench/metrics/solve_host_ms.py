"""Median per solve of the host's own time in ``engine.prepare``: the
span (fingerprint, sketch, upload, dispatch of the landmark path and
k-means) less the ``engine.wait`` spans inside it, where the host
blocks on a device result."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    return ms((p.seconds - sum(w.seconds for w in p.within("engine.wait"))
               for p in S.named(S.spans_of(run), "engine.prepare")
               if not p.stats.get("cached")), 50)
