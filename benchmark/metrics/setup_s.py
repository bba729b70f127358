"""Seconds from process start to the first measured call: imports,
device start, state from the seed, compile (or cache load), warm-up."""


def read(run):
    return run.setup_s
