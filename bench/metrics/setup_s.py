"""Seconds from process start to the window's first submission: rules,
traffic and weights from the seed, the rule compile, and the server's
build and warm-up, compiles included (host clock)."""


def read(run):
    return run.setup_s
