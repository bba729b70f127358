"""Landing layer (ops under ``mig:unpack``, the overlay kernel among
them): its device time against the time the step's arrivals' logical
bytes take at the HBM peak. Arrivals come from the per-step stats."""

from benchmark import work

SCOPE = "mig:unpack"
KERNEL = "_overlay_sorted_i8"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = t.time_s(lambda op: op.in_scope(SCOPE) or op.kernel == KERNEL)
    if secs <= 0:
        return None
    arrivals = sum(int(c["received"].sum()) for c in run.counters) / run.chips
    nbytes = work.landing_bytes(arrivals, run.shapes["K"])
    return 100.0 * nbytes / run.peaks.hbm_bytes_per_s / secs
