"""Full-population solves the engine published during the window,
divided by the window's length."""


def read(run):
    if not run.updates:
        return None
    count = sum(s["count"] for s in run.solves if s["t"] <= run.window_s)
    return count / run.window_s
