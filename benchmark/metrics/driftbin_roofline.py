"""Fused drift+wrap+bin kernel (``_driftbin_call``): its device time
against the time the layer's logical bytes take at the HBM peak."""

from benchmark import work

KERNEL = "_driftbin_call"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.kernel == KERNEL  # noqa: E731
    n = t.count(pred)
    secs = t.time_s(pred)
    if not n or secs <= 0:
        return None
    s = run.shapes
    nbytes = work.driftbin_bytes(s["slots_per_chip"], s["D"], s["K"]) * n
    return 100.0 * nbytes / run.peaks.hbm_bytes_per_s / secs
