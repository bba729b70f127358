"""Phase labels for profiler traces.

Two labeling helpers, one for each side of a jitted call:

* :func:`span` — host-side ``jax.profiler.TraceAnnotation`` wrapper: wrap
  host work (dispatch, transfers, snapshots) so a ``jax.profiler`` trace
  carries the caller's names on the same clock as the device ops.
* :func:`traced_span` — ``jax.named_scope`` wrapper for code INSIDE jit:
  attaches the name to the XLA ops it encloses (TraceAnnotation cannot
  reach into a compiled program). The migrate and exchange engines use
  it on every layer of a step (``mig:*``, ``rd:*``); a device op's
  ``op_name`` scope in the compiled HLO is how a trace reduction (the
  benchmark's ``benchmark/xplane.py``) puts its time down to a layer.
"""

from __future__ import annotations

import jax


def span(name: str):
    """Host-side profiler span: ``with span('exchange'): out = fn(x)``.

    Labels the enclosed HOST region in a ``jax.profiler.trace`` capture.
    For labels on the device ops themselves use :func:`traced_span`
    inside the traced function."""
    return jax.profiler.TraceAnnotation(name)


def traced_span(name: str):
    """Traced-code span: ``with traced_span('rd:bin'): dest = ...``.

    A ``jax.named_scope`` — the name lands in XLA op metadata, so
    Perfetto/XProf group the enclosed ops under it. Safe inside jit,
    scan bodies and shard_map (purely metadata; no ops inserted).
    """
    return jax.named_scope(name)
