"""Programmatic profiler sessions (ISSUE 14).

The one wrapper around ``jax.profiler``'s trace capture: it makes the
capture a SERVICE feature. :class:`ProfilerSession` is a context manager
any driver or CLI can hold around its hot region, gated by configuration
(``DriverConfig.profile_dir`` / the ``GRID_PROFILE_DIR`` env knob) so a
chip session captures traces without code edits, and journaled as a
``profile_session`` event so the capture is discoverable from the
journal alone (trace dir, wall duration, whether the profiler actually
armed).

Failure posture: profiling must never take the service down. A missing
directory knob disables the session outright (no event — the knob IS
the gate); an unavailable/broken ``jax.profiler`` degrades to a no-op
that still journals the attempt with ``armed=False`` and the error
string, because a silently missing trace on a chip session is exactly
the observability gap this subsystem exists to close.
"""

from __future__ import annotations

import os
import time
from typing import Optional

PROFILE_DIR_ENV = "GRID_PROFILE_DIR"


def profile_dir_from_env() -> Optional[str]:
    """The env-side knob (``GRID_PROFILE_DIR``); empty/unset = off."""
    d = os.environ.get(PROFILE_DIR_ENV, "").strip()
    return d or None


class ProfilerSession:
    """Gated ``jax.profiler`` trace session around a code region.

    ``with ProfilerSession(cfg.profile_dir, recorder=rec, label="run"):``
    — when ``log_dir`` is None the env knob is consulted; when both are
    unset the session is a guaranteed no-op (``enabled`` False, nothing
    journaled, jax never imported). Re-entrant use is an error only in
    jax; this wrapper surfaces it as a journaled failed arm, not a
    crash.
    """

    def __init__(
        self,
        log_dir: Optional[str] = None,
        recorder=None,
        label: str = "session",
    ):
        self.log_dir = log_dir if log_dir else profile_dir_from_env()
        self.recorder = recorder
        self.label = label
        self.enabled = self.log_dir is not None
        self.armed = False
        self.error: Optional[str] = None
        self._t0: Optional[float] = None

    def __enter__(self) -> "ProfilerSession":
        if not self.enabled:
            return self
        self._t0 = time.perf_counter()
        try:
            import jax

            jax.profiler.start_trace(self.log_dir)
            self.armed = True
        except Exception as e:  # profiling unavailable: degrade, never die
            self.error = f"{type(e).__name__}: {e}"
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self.enabled:
            return False
        duration = time.perf_counter() - (self._t0 or time.perf_counter())
        if self.armed:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                self.error = f"{type(e).__name__}: {e}"
                self.armed = False
        if self.recorder is not None:
            self.recorder.record(
                "profile_session",
                trace_dir=self.log_dir,
                label=self.label,
                duration_s=duration,
                armed=self.armed,
                error=self.error,
            )
        return False
