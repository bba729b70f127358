"""The one-call redistribute against its roofline: the time the least
bytes of a call (``work_oneshot.call_bytes``: each row read once and
written once) take at the HBM peak, over the device time of the ops
under every ``rd:`` scope."""

from benchmark import work_oneshot


def _redistribute(op) -> bool:
    return any(part.startswith("rd:") for part in op.scope)


def read(run):
    t = run.trace
    if t is None or not t.count(_redistribute):
        return None
    secs = t.time_s(_redistribute)
    if secs <= 0:
        return None
    s = run.shapes
    nbytes = work_oneshot.call_bytes(s["rows"], s["row_bytes"]) * run.calls
    return 100.0 * nbytes / run.peaks.hbm_bytes_per_s / secs
