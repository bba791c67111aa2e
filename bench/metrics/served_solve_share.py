"""Share of the traced window's published solves (``cohort.mailbox``)
that a later select swapped in and served; the rest were superseded in
the mailbox by a newer solve first.  Publishes after the window's last
select are left out (``bench/spans.py``, ``publish_fates``)."""

from bench import spans as S


def read(run):
    served, superseded, _ = S.publish_fates(S.spans_of(run))
    if not served + superseded:
        return None
    return 100.0 * served / (served + superseded)
