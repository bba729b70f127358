"""Timing helpers (SURVEY.md §5.1).

The driver metric is "particles redistributed/sec/chip; ICI all_to_all BW
utilization". Getting honest numbers on TPU needs care:

  * dispatch is async — a timing must end in a host fetch of the result;
  * compile, dispatch and transfer add a fixed per-invocation cost that
    swamps single-call timings of short steps.

:func:`scan_time_per_step` therefore compiles the step into ``lax.scan``
loops of two lengths and differences the wall times — compile, dispatch,
transfer and fetch costs cancel, leaving pure per-step device time. It is
exposed here for users profiling their own configurations. The repo's
benchmark (``benchmark/``) times whole calls on the host clock instead and
reads per-layer device time from profiler traces.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Tuple

import jax
import numpy as np


def fetch_barrier(pytree) -> None:
    """Hard barrier: force one device value to the host."""
    leaves = jax.tree.leaves(pytree)
    if leaves:
        np.asarray(jax.tree.map(lambda a: a.ravel()[0], leaves[0]))


def scan_time_per_step(
    make_loop: Callable[[int], Callable],
    args,
    s1: int = 8,
    s2: int = 72,
    reps: int = 2,
) -> Tuple[float, float, object]:
    """Per-step seconds of ``make_loop(S)(*args)`` via length differencing.

    ``make_loop(S)`` must return a jitted callable running S steps (e.g.
    ``lambda S: nbody.make_migrate_loop(cfg, mesh, S)``). Returns
    ``(per_step_seconds, fixed_overhead_seconds, long_loop_output)``;
    the overhead is the per-invocation cost the differencing removed
    (useful to sanity-check the method: it should dwarf neither
    measurement), and the long loop's output pytree lets callers inspect
    stats without paying another invocation.
    """
    if s2 <= s1:
        raise ValueError(f"need s2 > s1 for differencing, got {s1} >= {s2}")
    loops = {s: make_loop(s) for s in (s1, s2)}

    def run(s: int):
        out = loops[s](*args)
        fetch_barrier(out)  # warm: compile + first run
        times = []
        for _ in range(reps):
            # free the previous run's output BEFORE the next invocation:
            # at chip sizes the output pytree is GB-scale device state,
            # and holding two generations at once can be the marginal
            # allocation that exhausts device memory
            out = None
            t0 = time.perf_counter()
            out = loops[s](*args)
            fetch_barrier(out)
            times.append(time.perf_counter() - t0)
        return times, out

    times1, out1 = run(s1)
    del out1  # same: drop the short loop's state before the long compile
    times2, out2 = run(s2)
    t1 = min(times1)
    per_step = (min(times2) - t1) / (s2 - s1)
    return per_step, t1 - per_step * s1, out2


# Published per-chip peaks, keyed by JAX's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (system architecture table): 819 GB/s HBM
# bandwidth, 1,600 Gbit/s inter-chip interconnect (ICI), 197 TFLOP/s bf16
# per chip. The ICI figure is the chip's total over the 4 links of its 2D
# torus. A device kind that is not in this table is an error, never a
# default: a utilization against another chip's roof is a wrong number.
@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    hbm_bytes_per_sec: float
    ici_bytes_per_sec: float  # all links of one chip
    ici_links: int
    flops_per_sec: float  # bf16

    @property
    def ici_link_bytes_per_sec(self) -> float:
        return self.ici_bytes_per_sec / self.ici_links


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        hbm_bytes_per_sec=819e9,
        ici_bytes_per_sec=1600e9 / 8,
        ici_links=4,
        flops_per_sec=197e12,
    ),
}
TARGET_KIND = "TPU v5 lite"  # the chip this repo is built and benched for


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip; raises for an unknown kind."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)})"
        ) from None


# The ANALYTIC models (telemetry/roofline.py, phases.py, flow.py) price a
# program against the target chip's roofs; they measure nothing.
# Exchange domains: "hbm" is the single-chip vrank exchange, whose "wire"
# is HBM-side gathers/scatters; "ici" is the cross-chip all_to_all, whose
# traffic spreads over every link, so its per-chip roof is the link sum.
HBM_PEAK_BYTES_PER_SEC = CHIP_PEAKS[TARGET_KIND].hbm_bytes_per_sec
ICI_LINK_BYTES_PER_SEC = CHIP_PEAKS[TARGET_KIND].ici_link_bytes_per_sec
ICI_LINKS_PER_CHIP = CHIP_PEAKS[TARGET_KIND].ici_links
# the engines run f32 elementwise/gather work on the VPU, not MXU
# matmuls, so the bf16 figure is an upper bound — it keeps every
# "compute-bound" verdict conservative
PEAK_FLOPS_PER_SEC = CHIP_PEAKS[TARGET_KIND].flops_per_sec


def exchange_peak_bytes_per_sec(domain: str,
                                device_kind: str = TARGET_KIND) -> float:
    """Peak bytes/s for an exchange domain, per chip of ``device_kind``.

    ``domain`` is the report's ``exchange_domain``: ``"hbm"`` when
    the vrank exchange stays on one chip, ``"ici"`` when rows ride the
    inter-chip all_to_all (all links active).
    """
    peaks = chip_peaks(device_kind)
    if domain == "hbm":
        return peaks.hbm_bytes_per_sec
    if domain == "ici":
        return peaks.ici_bytes_per_sec
    raise ValueError(f"unknown exchange domain {domain!r}")


def exchange_bw_util(
    bytes_per_sec: float, domain: str, n_chips: int = 1,
    device_kind: str = TARGET_KIND,
) -> float:
    """Fraction of the domain's peak bandwidth the exchange achieves.

    This completes the BASELINE metric: ``exchange_bytes_per_sec`` divided
    by the peak for the domain it crossed (HBM on one chip, summed ICI
    links per chip otherwise). ``bytes_per_sec`` should be aggregate
    payload bytes / step time; for multi-chip runs pass the aggregate and
    the chip count so the per-chip figure is compared to a per-chip roof.
    """
    return bytes_per_sec / n_chips / exchange_peak_bytes_per_sec(
        domain, device_kind
    )


def measured_bw_util(bytes_per_sec: float, domain: str, n_chips: int = 1,
                     device=None):
    """:func:`exchange_bw_util` for a rate timed on ``device`` (default:
    the first JAX device): ``"not measured"`` unless it is a TPU, whose
    kind must be in :data:`CHIP_PEAKS`."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return "not measured"
    return exchange_bw_util(
        bytes_per_sec, domain, n_chips, device.device_kind
    )


def exchange_bytes_per_step(stats, row_bytes: int) -> float:
    """Mean bytes crossing the exchange per step, from a stats pytree.

    Works for both ``RedistributeStats`` (send_counts [R, R], optionally
    step-stacked to [S, R, R]) and ``MigrateStats`` (sent [R] or [S, R]);
    multiply by achieved step rate for wire bandwidth, compare against
    ICI line rate for utilization.
    """
    if hasattr(stats, "sent"):
        sent = np.asarray(stats.sent)
        # normalize to [S, R]: a single-call stats pytree has no step axis
        sent = sent.reshape(-1, sent.shape[-1])
    else:
        sent = np.asarray(stats.send_counts)
        sent = sent.reshape((-1,) + sent.shape[-2:])  # [S, R, R]
    per_step = sent.reshape(sent.shape[0], -1).sum(axis=-1)
    return float(per_step.mean()) * row_bytes
