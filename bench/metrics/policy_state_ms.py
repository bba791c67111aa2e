"""Median per round of the summed ``policy.state`` spans: the serving
state (a float64 dispersion over the whole table) built once for the
select's draw and once for ``observe_round``'s next state.  A round is
a ``cohort.select`` and the ``cohort.observe`` with its ``seq``; one the
trace window cut in half is left out."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    spans = S.spans_of(run)
    observes = {s.stats.get("seq"): s
                for s in S.named(spans, "cohort.observe")}
    rounds = [sum(p.seconds for p in sel.within("policy.state")
                  + observes[sel.stats.get("seq")].within("policy.state"))
              for sel in S.named(spans, "cohort.select")
              if sel.stats.get("seq") in observes]
    return ms(rounds, 50)
