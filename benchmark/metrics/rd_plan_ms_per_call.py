"""Device ms per call of the one-call plan: ops under ``rd:bin`` (the
owner of every row and the destination sort) and ``rd:pack`` (the
gather of each destination's columns), mean over the chips."""

SCOPES = ("rd:bin", "rd:pack")


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(*SCOPES)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.calls * 1e3
