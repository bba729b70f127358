"""95th percentile (nearest rank) of every call's host-clock time, from
dispatch until its outputs are ready and its stats are on the host."""

from benchmark import timing


def read(run):
    return timing.percentile(run.call_s, 95) * 1e3
