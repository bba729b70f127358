"""Device ms per step of the free-slot stack: ops under ``mig:stack``
(landing targets, the pops window, the stack's push and pop, and the
count of landed rows), mean over the chips."""

SCOPE = "mig:stack"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(SCOPE)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.steps * 1e3
