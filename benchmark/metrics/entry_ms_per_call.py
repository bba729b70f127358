"""Device ms per call of the entry layer: ops under ``mig:enter`` (the
planar fuse and the free-stack argsort before the steps) and
``mig:exit`` (the planar split after them), mean over the chips."""

SCOPES = ("mig:enter", "mig:exit")


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(*SCOPES)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.calls * 1e3
