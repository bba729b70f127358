"""Phase attribution: the knockout technique as a reusable API.

``scripts/knockout_stages.py`` established the repo's attribution method:
compile the step truncated after each phase, time each truncation with
scan-length differencing (:func:`..utils.profiling.scan_time_per_step` —
compile/dispatch costs cancel), and read per-phase cost off the
deltas, optionally against a logical-bytes roofline. That script remains
the maintained copy of the migrate step; THIS module owns the harness, so
any loop builder — knockout copies, ablation variants, user pipelines —
gets the same protocol and the same table without re-deriving it.

Two labeling helpers complete the picture for trace-based profiling:

* :func:`span` — host-side ``jax.profiler.TraceAnnotation`` wrapper: wrap
  dispatch regions so Perfetto/XProf timelines carry the caller's names.
* :func:`traced_span` — ``jax.named_scope`` wrapper for code INSIDE jit:
  attaches the name to the XLA ops it encloses (TraceAnnotation cannot
  reach into a compiled program). The exchange/migrate engines use it on
  their bin/pack/exchange/unpack phases.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Sequence

import jax

from mpi_grid_redistribute_tpu.utils import profiling


def span(name: str):
    """Host-side profiler span: ``with span('exchange'): out = fn(x)``.

    Labels the DISPATCH of the enclosed region in a ``jax.profiler.trace``
    capture. For labels on the device ops themselves use
    :func:`traced_span` inside the traced function."""
    return jax.profiler.TraceAnnotation(name)


def traced_span(name: str):
    """Traced-code span: ``with traced_span('rd:bin'): dest = ...``.

    A ``jax.named_scope`` — the name lands in XLA op metadata, so
    Perfetto/XProf group the enclosed ops under it. Safe inside jit,
    scan bodies and shard_map (purely metadata; no ops inserted).
    """
    return jax.named_scope(name)


class PhaseTiming(NamedTuple):
    """One row of an attribution run. ``cumulative_s`` is the truncated
    step's per-step time; ``delta_s`` the increment over the previous
    phase (the phase's attributed cost); roofline fields are populated
    when logical bytes were supplied."""

    phase: object
    cumulative_s: float
    delta_s: float
    logical_bytes: Optional[int] = None
    roofline_s: Optional[float] = None

    @property
    def x_roofline(self) -> Optional[float]:
        """measured delta / roofline time; >>1 flags latency/serialization
        bound (scatters, sorts), not a bandwidth wall."""
        if not self.roofline_s or self.roofline_s <= 0:
            return None
        return self.delta_s / self.roofline_s


def attribute_phases(
    loop_builder: Callable[[object, int], Callable],
    args,
    phases: Sequence,
    *,
    s1: int = 4,
    s2: int = 16,
    reps: int = 2,
    phase_bytes: Optional[dict] = None,
    peak_bytes_per_sec: float = profiling.HBM_PEAK_BYTES_PER_SEC,
    progress: Optional[Callable[[PhaseTiming], None]] = None,
) -> List[PhaseTiming]:
    """Attribute a step's time to its phases by cumulative truncation.

    Args:
      loop_builder: ``loop_builder(phase, S)`` must return a jitted
        callable running ``S`` steps of the pipeline truncated after
        ``phase`` (phases are caller-defined tokens — ints, names).
        Each truncation must keep a data dependency on its last phase's
        output so XLA cannot dead-code-eliminate the work (see
        ``scripts/knockout_stages.py`` ``dep_out`` for the idiom).
      args: loop inputs, passed through to the built loops.
      phases: ordered phase tokens; deltas attribute ``phases[i]``'s cost
        as ``cumulative[i] - cumulative[i-1]`` (the first row's delta is
        its cumulative time — everything up to and including it).
      s1/s2/reps: scan-differencing protocol knobs
        (:func:`..utils.profiling.scan_time_per_step`).
      phase_bytes: optional ``{phase: logical_bytes}`` — minimum traffic
        each phase's math implies; fills the roofline columns.
      peak_bytes_per_sec: roofline denominator (defaults to the v5e HBM
        peak; pass an ICI roof for wire-bound phases).
      progress: optional callback invoked with each finished row (the
        knockout script streams its table through this).

    Returns one :class:`PhaseTiming` per phase, in order.
    """
    out: List[PhaseTiming] = []
    prev = None
    for phase in phases:
        per_step, _overhead, _last = profiling.scan_time_per_step(
            lambda S, phase=phase: loop_builder(phase, S),
            args, s1=s1, s2=s2, reps=reps,
        )
        del _last  # GB-scale output pytrees must not pile up across phases
        delta = per_step if prev is None else per_step - prev
        lb = None if phase_bytes is None else phase_bytes.get(phase)
        roof = None if lb is None else lb / peak_bytes_per_sec
        row = PhaseTiming(phase, per_step, delta, lb, roof)
        out.append(row)
        if progress is not None:
            progress(row)
        prev = per_step
    return out


def format_phase_table(timings: Sequence[PhaseTiming]) -> str:
    """Markdown knockout table (the BENCH_CONFIGS.md format): cumulative
    ms, delta ms, logical MB, roofline ms, x-roofline."""
    lines = [
        "| phase (cumulative) | ms | delta | logical MB | roofline ms "
        "| x-roofline |",
        "|---|---|---|---|---|---|",
    ]
    for i, t in enumerate(timings):
        mb = "—" if t.logical_bytes is None else f"{t.logical_bytes/1e6:8.1f}"
        roof = "—" if t.roofline_s is None else f"{t.roofline_s*1e3:6.2f}"
        xr = t.x_roofline
        xcol = "—" if xr is None else f"{xr:6.1f}"
        delta = "(first)" if i == 0 else f"{t.delta_s*1e3:+7.2f}"
        lines.append(
            f"| {t.phase} | {t.cumulative_s*1e3:7.2f} | {delta} | {mb} "
            f"| {roof} | {xcol} |"
        )
    return "\n".join(lines)
