"""Device ms per step of the drift+bin layer: ops under ``mig:drift``
(the fused drift+wrap+bin kernel on one chip, the XLA drift and wrap
elsewhere), mean over the chips."""

SCOPE = "mig:drift"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(SCOPE)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.steps * 1e3
