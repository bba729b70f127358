"""Device ms per step of the grant tables: ops under ``mig:grant`` (the
receivers' grants and their all_to_alls across chips, the swap and
residual allocation, the fast-path guard), mean over the chips."""

SCOPE = "mig:grant"


def read(run):
    t = run.trace
    if t is None:
        return None
    pred = lambda op: op.in_scope(SCOPE)  # noqa: E731
    if not t.count(pred):
        return None
    return t.time_s(pred) / run.steps * 1e3
