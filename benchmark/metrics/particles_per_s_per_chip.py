"""Live particles x steps completed / window seconds / chips: all the
work over all the time of the window (host clock)."""

from benchmark import timing


def read(run):
    return timing.rate(run.units_per_call, run.calls, run.window_s, run.chips)
