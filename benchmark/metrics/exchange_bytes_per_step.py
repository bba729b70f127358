"""Bytes of migrant rows per step per chip: rows sent (the program's
per-step stats) x row bytes, over the calls of the traced window. A
count that repeats exactly for a seed."""


def read(run):
    if not run.counters:
        return None
    sent = sum(int(c["sent"].sum()) for c in run.counters)
    return sent * run.shapes["row_bytes"] / run.steps / run.chips
