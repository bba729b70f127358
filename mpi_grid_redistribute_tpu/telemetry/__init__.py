"""Unified telemetry: the always-on observability layer (SURVEY.md §5.1/§5.5).

Turns the scattered instruments that grew around the engines — the
scan-differencing timers in :mod:`..utils.profiling`, the stats summaries
in :mod:`..utils.stats` — into one subsystem with three pieces:

* :mod:`.recorder` — a bounded host-side ring buffer of structured events
  (capacity growth, overflow window scheduling/resolution, halo cap
  growth, per-step exchange counters) with JSONL export. Every
  :class:`~..api.GridRedistribute` owns one as ``rd.telemetry``.
* :mod:`.phases` — ``span()``/``traced_span()`` label host regions
  (``jax.profiler.TraceAnnotation``: ``host:*`` spans on the service and
  API paths) and traced regions (``jax.named_scope`` → XLA op metadata:
  ``mig:*``, ``rd:*``, ``svc:*``, ``pipe:*``), so a profiler trace puts
  every device op of a step down to a layer, not op soup.
* :mod:`.report` — the metrics surface: one merged dict (stats summary,
  exchange bytes/step, achieved GB/s, ``bw_util`` against the HBM/ICI
  roofs in :mod:`..utils.profiling`, growth/overflow event counts),
  reachable as ``rd.report()``.

The grid observatory (PR 3) adds three layers on that substrate:

* :mod:`.flow` — per-link flow attribution: the in-graph ``[R, R]``
  flow matrix both engines stack into their stats pytrees,
  :class:`~.flow.FlowAccumulator` host gauges (EMA + cumulative +
  imbalance + hot pairs), ``flow_snapshot`` journal events, per-link
  ``bw_util`` in :func:`~.report.exchange_report`.
* :mod:`.health` — an always-on :class:`~.health.HealthMonitor`
  evaluating declarative rules (backlog growth, dropped rows, grow
  frequency, imbalance, step-time spikes) over the journal; findings
  fire callbacks and land as ``alert`` events in the same ring.
* :mod:`.traceview` — Perfetto/Chrome-trace JSON export of the journal
  and migrate counter tracks (``scripts/trace_export.py``;
  ``rd.to_perfetto()``).

The metrics plane (ISSUE 5) makes the journal scrapable pod-wide:

* :mod:`.metrics` — Counter/Gauge/Histogram (pow2 buckets) registry,
  ``from_journal()`` replay into standard grid families, OpenMetrics
  text rendering (``render_openmetrics``); served live by
  ``scripts/metrics_serve.py`` (``/metrics`` + ``/healthz``) and
  reachable as ``rd.metrics()``.
* :mod:`.aggregate` — multi-host journal aggregation:
  ``merge_journals()`` k-way merges per-process JSONL shards
  (``host``/``pid``-tagged lines) with monotone-repaired clock
  alignment; the :class:`~.aggregate.MergedJournal` projects back into
  a pod-wide recorder, ``MigrateStats``-shaped pod stats for
  :func:`~.report.exchange_report`, and merged flow gauges.

Device time is read from profiler traces:

* :mod:`.profiler` — :class:`~.profiler.ProfilerSession`, the gated
  programmatic ``jax.profiler`` trace wrapper (``GRID_PROFILE_DIR`` /
  ``DriverConfig.profile_dir``), journaled as ``profile_session``
  events. Its trace holds the device ops under their layer scopes and
  the host spans of :mod:`.phases` on one clock.

The incident observatory (ISSUE 17) makes the journal causal and the
alerts actionable:

* :mod:`.context` — thread-local :class:`~.context.StepContext`
  (trace id, step/call index, restart attempt, origin thread) merged
  into every event envelope by the recorder; "which step caused this
  alert/restart" becomes a join on ``trace``/``ctx_*`` fields.
* :mod:`.incident` — the :class:`~.incident.FlightRecorder` health
  callback: on ALERT (or injected fault) it
  freezes a debounced incident bundle — journal window, counts,
  OpenMetrics text, health findings, flow snapshot, env fingerprint,
  triggering step context — under an ``index.json``
  (``scripts/incident.py`` CLI; ``GET /incidents`` on the metrics
  server).
* :mod:`.health` additionally grew multi-window error-budget burn-rate
  rules (``burn_rate_latency`` / ``burn_rate_dropped``) and isolates
  callback exceptions (``callback_error`` events).

The telemetry history plane (ISSUE 18) makes the journal durable and
queryable:

* :mod:`.store` — :class:`~.store.JournalStore`, a segmented
  append-only store the service driver drains the recorder ring into
  at every chunk/health boundary: size/step rotation, sha256-manifest
  integrity (checkpoint staged-rename publishes), age/byte retention,
  and compaction of old raw segments into exact ``store_window``
  summaries (per-kind counts + quantile sketches on the live Histogram
  edges) — bounded disk with byte-exact all-time counts after ring
  eviction (:class:`~.store.StoreReader`; ``scripts/storecheck.py``
  gates ST01–ST07).
* :mod:`.query` — the jax-free query plane over any journal source
  (live recorder, merged shards, store): kind/step/trace/host/ctx
  filters, windowed aggregations (rate, p50/p99, EMA), group-bys —
  served as ``GET /query`` plus the cursor-resumable ``GET /events``
  long-poll on ``scripts/metrics_serve.py``; ``scripts/grid_top.py``
  is the live terminal dashboard and ``scripts/history.py`` the
  cross-run index.

The state-health observatory (ISSUE 20) watches the *physics*, not
just the system:

* :mod:`.probes` — the host side of the in-graph invariant probes
  (``ops/statehealth.py``): :class:`~.probes.ProbeConfig` (static
  off/counters/moments tier; off is bit-identical zero-cost),
  ``record_probe_steps`` journaling one ``state_health`` event per
  scanned step (NaN/Inf rows, out-of-bounds positions, the exact int32
  conservation residual, optional moments), and ``summarize_host``,
  the counter-exact numpy mirror for the driver's eager path.
* :mod:`.health` additionally grew the ``nan_detected`` /
  ``conservation_drift`` / ``bounds_violation`` ALERT rules; the
  driver's boundary gate turns their findings into a
  ``StateCorruptionError`` restart BEFORE the snapshot hook, so the
  supervisor restores a pre-corruption snapshot.

Event schema and metric families: ``telemetry/SCHEMA.md``.
"""

from mpi_grid_redistribute_tpu.telemetry.recorder import (  # noqa: F401
    Event,
    StepRecorder,
    fast_path_hit_rate,
    record_chunk_steps,
    record_fast_path_steps,
    record_migrate_steps,
)
from mpi_grid_redistribute_tpu.telemetry.phases import (  # noqa: F401
    span,
    traced_span,
)
from mpi_grid_redistribute_tpu.telemetry.report import (  # noqa: F401
    exchange_report,
    row_bytes_of,
)
from mpi_grid_redistribute_tpu.telemetry.metrics import (  # noqa: F401
    MetricsRegistry,
    from_journal,
    pow2_edges,
    render_openmetrics,
)
from mpi_grid_redistribute_tpu.telemetry.aggregate import (  # noqa: F401
    MergedJournal,
    merge_journals,
)
from mpi_grid_redistribute_tpu.telemetry.flow import (  # noqa: F401
    FlowAccumulator,
    flow_matrix_of,
    link_report,
    record_flow_snapshot,
)
from mpi_grid_redistribute_tpu.telemetry.health import (  # noqa: F401
    Finding,
    HealthMonitor,
    HealthRule,
    bounds_violation,
    burn_rate_dropped,
    burn_rate_latency,
    conservation_drift,
    default_rules,
    fast_path_fallback,
    nan_detected,
    snapshot_staleness,
)
from mpi_grid_redistribute_tpu.telemetry.probes import (  # noqa: F401
    ProbeConfig,
    record_probe_steps,
    summarize_host,
)
from mpi_grid_redistribute_tpu.telemetry.context import (  # noqa: F401
    StepContext,
)
from mpi_grid_redistribute_tpu.telemetry.incident import (  # noqa: F401
    FlightRecorder,
    list_bundles,
    load_bundle,
)
from mpi_grid_redistribute_tpu.telemetry.traceview import (  # noqa: F401
    to_chrome_trace,
    write_trace,
)
from mpi_grid_redistribute_tpu.telemetry.profiler import (  # noqa: F401
    ProfilerSession,
)
from mpi_grid_redistribute_tpu.telemetry.tsan import (  # noqa: F401
    ThreadAccess,
    ThreadAccessTracer,
)
from mpi_grid_redistribute_tpu.telemetry.store import (  # noqa: F401
    JournalStore,
    StoreCorruptError,
    StoreReader,
    list_stores,
)
from mpi_grid_redistribute_tpu.telemetry.query import (  # noqa: F401
    QueryError,
    events_page,
    filter_rows,
    group_rows,
    rows_of,
    run_query,
    window_aggregate,
)
