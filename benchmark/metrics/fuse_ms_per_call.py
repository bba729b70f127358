"""Device ms per call of the API boundary: ops under ``rd:fuse`` (the
planar fuse of the caller's row-major arrays, 8-byte columns split into
their words) and ``rd:unfuse`` (the split back to row-major outputs),
mean over the chips."""

SCOPES = ("rd:fuse", "rd:unfuse")


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(*SCOPES)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.calls * 1e3
