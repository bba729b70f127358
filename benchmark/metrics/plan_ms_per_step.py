"""Device ms per step of the plan layer: ops under ``mig:select`` and
``mig:pack``, mean over the chips."""

SCOPES = ("mig:select", "mig:pack")


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(*SCOPES)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.steps * 1e3
