"""Share of the traced window's ``policy.state`` spans (the select's and
the observe's) whose per-solve statistics (population share, dispersion)
the server reused from its memo of the served solve (``hit`` = 1) rather
than built.  None where no ``policy.state`` span carries ``hit`` (a
program without the memo)."""

from bench import spans as S


def read(run):
    hits = [int(s.stats["hit"]) for s in S.named(S.spans_of(run),
                                                 "policy.state")
            if "hit" in s.stats]
    return 100.0 * sum(hits) / len(hits) if hits else None
