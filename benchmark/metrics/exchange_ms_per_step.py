"""Device ms per step of the exchange layer: ops under ``mig:exchange``
(the all_to_all across chips), mean over the chips."""

SCOPE = "mig:exchange"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(SCOPE)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.steps * 1e3
