"""Seconds from process start to window open: start-up, weights, the
engine's instances, compilation or its cache, and the warm-up."""


def read(run):
    return run.setup_s
