"""Seconds from process start to the window's open: weights, engine build,
compiles or cache loads, and warm-up (host clock)."""


def read(run):
    return run["setup_s"]
