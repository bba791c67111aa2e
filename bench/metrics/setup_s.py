"""Seconds from process start to the window: imports, the table, the
server, the compile cache or compiler, and the warm-up solves."""


def read(run):
    return run.setup_s
