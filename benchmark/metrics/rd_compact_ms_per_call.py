"""Device ms per call of the one-call landing: ops under ``rd:unpack``,
the payload-carrying compaction sort into Alltoallv receive order, mean
over the chips."""

SCOPE = "rd:unpack"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(SCOPE)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.calls * 1e3
