"""Long-running service driver: the drift→redistribute loop as a process.

Everything else in the repo runs the loop for a fixed number of steps and
exits with its process; this module is ROADMAP item 3's first half — the
loop as an *always-on service*. :class:`ServiceDriver` owns the particle
state, advances it through the public :class:`~..api.GridRedistribute`
engine step after step, and on a step cadence:

* snapshots the full particle pytree through the hardened
  ``utils/checkpoint.py`` (atomic publish + per-shard checksums), by
  default on a background writer thread so the write overlaps the next
  steps instead of stalling them (the <= 2% overhead budget,
  ``tests/test_service.py``);
* exports its journal as a per-process JSONL shard (the metrics plane's
  scrape substrate), detecting and healing a lost shard;
* evaluates the :class:`~..telemetry.health.HealthMonitor` rules, and
  degrades ``engine -> planar`` exactly once if the
  ``fast_path_fallback`` rule fires (journaled ``degrade``; a one-way
  ratchet, never flapping).

A wall-clock watchdog turns a stalled step into a
:class:`~.faults.StallError` — a *failure* the supervisor restarts from
snapshot, not a silent wait. All state transitions are journaled
(``snapshot`` / ``restore`` / ``degrade``; see telemetry/SCHEMA.md) so
the recovery story is auditable from the journal alone.

The step itself is deliberately deterministic: host-side float32 drift +
periodic wrap, then one public-API redistribute. Restoring a snapshot at
step k and running to step N is bit-identical to an uninterrupted run to
N — the property ``pod_smoke --kill-restore`` and the fault-matrix tests
assert, and the foundation for elastic restarts (a snapshot written at R
shards reloads at any shard count, ``utils/checkpoint.py``).

CLI (used by ``scripts/pod_smoke.py --kill-restore``)::

    python -m mpi_grid_redistribute_tpu.service.driver \\
        --grid 2,2,2 --steps 60 --snapshot-every 5 --snapshot-dir /tmp/snaps

"""
# gridlint: service-path

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional, Tuple

import numpy as np

from mpi_grid_redistribute_tpu.service.faults import FaultPlan, StallError
from mpi_grid_redistribute_tpu.telemetry import StepRecorder
from mpi_grid_redistribute_tpu.telemetry import context as context_lib
from mpi_grid_redistribute_tpu.telemetry.health import HealthMonitor
from mpi_grid_redistribute_tpu.telemetry.phases import span
from mpi_grid_redistribute_tpu.telemetry.probes import (
    ProbeConfig,
    record_probe_steps,
    summarize_host,
)
from mpi_grid_redistribute_tpu.telemetry.profiler import ProfilerSession
from mpi_grid_redistribute_tpu.utils import checkpoint


@dataclasses.dataclass(frozen=True)
class DriverConfig:
    """Static configuration of one service run (hashable, restart-safe:
    two drivers built from the same config are interchangeable)."""

    grid_shape: Tuple[int, ...] = (2, 2, 2)
    n_local: int = 4096       # padded rows per shard (state shape, fixed)
    # live fraction: per-rank population is a bounded random walk around
    # uniform, so the 1-fill headroom must cover several sigma of
    # sqrt(live) Poisson-scale skew or a long soak eventually drops
    # arrivals (0.9 measurably overflows at n_local ~ 1k)
    fill: float = 0.8
    steps: int = 64           # service horizon (CLI/tests; soak loops run())
    dt: float = 1.0
    seed: int = 0
    migration: float = 0.02   # ~fraction of live rows crossing a face/step
    backend: str = "jax"      # "jax" | "numpy" (oracle; meshless)
    engine: str = "auto"
    snapshot_every: int = 0   # steps between snapshots; 0 = snapshots off
    snapshot_dir: Optional[str] = None
    keep_snapshots: int = 4   # retained snapshots (>= 2: torn-skip fallback)
    snapshot_async: bool = True
    journal_dir: Optional[str] = None
    watchdog_s: float = 0.0   # wall budget per step; 0 = watchdog off
    health_every: int = 0     # extra health cadence; 0 = at snapshots only
    step_sleep: float = 0.0   # pacing, so external kills land mid-run
    # resident chunked stepping (ISSUE 10): advance `chunk` steps per
    # dispatch as ONE jitted lax.scan (service/resident.py) with the
    # per-step observables carried in-graph as scan ys; the host reads
    # back only the ys and the final carry at chunk boundaries, and
    # snapshot/health/fault hooks land exactly there (the chunk is
    # auto-split at the next scheduled boundary, so cadences and the
    # deterministic fault matrix are honored bit-for-bit). chunk=1 is
    # today's eager loop; the numpy oracle backend batches the same
    # boundary bookkeeping without a device scan.
    chunk: int = 1
    # software-pipelined macro-step (ISSUE 12): overlap each step's
    # exchange with the next step's drift/binning inside the resident
    # scan (service/pipeline.py). Build-time infeasible schedules
    # (chunk < 2, non-planar payload, ragged capacities, multi-device
    # topology) degrade to the sequential body, journaled as
    # engine_resolved; chunk auto-split rules are unchanged.
    pipeline: bool = False
    # state-health observatory (ISSUE 20): probe tier folded into the
    # resident/pipelined macro-step ("off" | "counters" | "moments",
    # telemetry/probes.py). Armed tiers journal one `state_health`
    # event per step (NaN/Inf, out-of-bounds and conservation-ledger
    # counters; "moments" adds extents and the velocity second moment)
    # and any nonzero corruption counter fails the NEXT chunk boundary
    # with StateCorruptionError BEFORE the snapshot hook — the newest
    # snapshot always predates the corruption, so the supervisor's
    # restore rolls the damage back. "off" is bit-identical zero-cost:
    # the builders emit the exact unprobed program.
    probes: str = "off"
    # elastic restore (ISSUE 8): re-shard a snapshot whose (nranks,
    # rows_per_shard) disagrees with this config onto the configured
    # grid in one canonical redistribute; off = clear ElasticRestoreError
    auto_reshard: bool = True
    # SLO surface feeding the restart policy; each knob, when enabled,
    # installs its ALERT rule and a breach raises SLOBreachError out of
    # the run loop (restart; repeated breach = supervisor mesh shrink)
    slo_latency_p99_s: float = 0.0   # p99 step-latency budget; 0 = off
    slo_dropped_p99: int = -1        # p99 dropped-rows budget; -1 = off
    slo_window: int = 16             # step_latency events per SLO window
    # adaptive rebalancing (ROADMAP item 2): the imbalance_ratio rule is
    # raised to ALERT severity at `rebalance_threshold`, and each firing
    # at a health boundary runs plan (telemetry.rebalance.RebalancePlanner,
    # fine-cell occupancy -> LPT) -> amortization guard -> one-shot
    # GridRedistribute.apply_assignment, journaling a `rebalance` event
    # whether it applied or declined (telemetry/SCHEMA.md)
    rebalance: bool = False
    # health rules whose ALERT findings actuate the rebalance loop: the
    # population-skew gauge (imbalance_ratio) and the queueing signal
    # (backlog_growth, already ALERT severity in the stock rule set).
    # The triggering rule is journaled on every `rebalance` event.
    rebalance_on: Tuple[str, ...] = ("imbalance_ratio", "backlog_growth")
    rebalance_threshold: float = 2.0  # imbalance_ratio ALERT threshold
    rebalance_cells: int = 2          # fine cells per grid cell per axis
    rebalance_horizon: int = 256      # guard amortization horizon (steps)
    rebalance_cooldown: int = 64      # min steps between applied remaps
    rebalance_min_improvement: float = 0.05
    # profiler sessions (ISSUE 14): when set (or via GRID_PROFILE_DIR),
    # run() wraps the whole stepping loop in a
    # telemetry.profiler.ProfilerSession — one jax.profiler trace into
    # this directory per run() call, journaled as a profile_session
    # event. None = off; an unavailable profiler degrades to a no-op
    # (armed=False in the event), never a crash.
    profile_dir: Optional[str] = None
    # incident observatory (ISSUE 17): when set, a
    # telemetry.incident.FlightRecorder is attached to the health
    # monitor — every ALERT finding (plus injected faults scanned at
    # boundaries/close) freezes a debounced incident bundle into this
    # directory. The flight recorder is keyed on the shared journal so
    # its debounce/counter state survives supervisor restarts.
    incident_dir: Optional[str] = None
    incident_debounce_s: float = 60.0  # per-rule bundle debounce window
    # telemetry history plane (ISSUE 18): when set, a
    # telemetry.store.JournalStore rooted here is drained at every
    # chunk/health boundary (and once more at close()) — the bounded
    # recorder ring becomes durable checksummed segments with the
    # recorder's exact all-time counts in the manifest. Drains happen
    # only at boundaries, never inside the resident macro-step (G009),
    # and a restarted driver re-opens the same root and resumes from
    # the manifest's drain watermark (no duplicate events). Inspect
    # with scripts/grid_top.py / scripts/storecheck.py, serve with
    # scripts/metrics_serve.py --store.
    store_dir: Optional[str] = None
    store_segment_events: int = 4096   # events per segment before rotation
    store_retain_bytes: int = 64 * 1024 * 1024  # closed-segment disk budget
    store_compact_after: int = 2       # newest raw segments kept uncompacted
    # multi-window error-budget burn-rate alerting over the same SLO
    # thresholds (telemetry.health.burn_rate_*): pure alerting — burn
    # ALERTs capture bundles and flip /healthz but do not raise
    # SLOBreachError mid-run (the point-in-time slo_* rules own the
    # restart actuation). Windows are (slo_window, 4 * slo_window).
    burn_rate_alerts: bool = False


class ServiceDriver:
    """One supervised instance of the streaming loop.

    Lifecycle: ``restore_latest()`` (or ``init_state()``), ``run()``,
    ``close()``. The supervisor builds a fresh driver per restart from
    the same config + shared recorder; all recovery state lives in
    snapshots and the journal, never in the object.
    """

    def __init__(
        self,
        cfg: DriverConfig,
        recorder: Optional[StepRecorder] = None,
        monitor: Optional[HealthMonitor] = None,
        faults: Optional[FaultPlan] = None,
    ):
        if cfg.snapshot_every and not cfg.snapshot_dir:
            raise ValueError("snapshot_every set but snapshot_dir is None")
        if cfg.snapshot_every and cfg.keep_snapshots < 2:
            raise ValueError(
                "keep_snapshots must be >= 2 so a corrupt newest snapshot "
                "always has a valid predecessor to fall back to"
            )
        self.cfg = cfg
        self.recorder = recorder if recorder is not None else StepRecorder()
        self.monitor = (
            monitor if monitor is not None else HealthMonitor(self.recorder)
        )
        self.faults = faults if faults is not None else FaultPlan()
        self.engine = cfg.engine
        self.degraded = False
        self.step = 0
        self.state: Optional[Tuple[np.ndarray, ...]] = None
        self.journal_path: Optional[str] = None
        self._rd = None
        self._wall_ema: Optional[float] = None
        self._last_dropped = 0
        self._writer: Optional[threading.Thread] = None
        self._writer_error: Optional[str] = None
        # guards _writer_error: written by the snapshot-writer thread,
        # read-and-cleared (exactly once) by join_snapshot_writer
        self._writer_lock = threading.Lock()
        self._last_snapshot_path: Optional[str] = None
        # adaptive rebalancing: the current assignment-aware edges (must
        # survive engine rebuilds — a degrade that dropped them would
        # silently undo the rebalance), plus lazily-built planner/guard
        self._edges = None
        self._planner = None
        self._guard = None
        # resident chunked stepping: compiled macro-step cache, keyed on
        # everything that changes the traced program (chunk length,
        # layout, capacities, mover block, edges, engine), plus the
        # completion timestamp of the last retired chunk — the timing
        # anchor that keeps per-step walls honest when chunk k+1 was
        # dispatched before chunk k's host reads (async overlap)
        self._chunk_cache = {}
        self._chunk_done: Optional[float] = None
        # state-health observatory (ISSUE 20): the static probe config
        # (validates cfg.probes eagerly; joins the macro cache key) and
        # the breach latch a probed chunk sets when any corruption
        # counter is nonzero — consumed by _state_health_gate at the
        # NEXT boundary, before the snapshot hook
        self._probes = ProbeConfig(tier=cfg.probes)
        self._state_breach = False
        self._install_slo_rules()
        self._install_rebalance_rule()
        self._flight = self._install_flight_recorder()
        self._store = self._install_store()

    def _install_slo_rules(self) -> None:
        # the monitor is SHARED across supervisor restarts, so install
        # by rule name, never append blindly (a restarted driver must
        # not stack a second copy of each rule)
        from mpi_grid_redistribute_tpu.telemetry import health as health_lib

        cfg = self.cfg
        have = {r.name for r in self.monitor.rules}
        if cfg.slo_latency_p99_s > 0 and "slo_latency_p99" not in have:
            self.monitor.rules.append(
                health_lib.slo_latency_p99(
                    cfg.slo_latency_p99_s, window=cfg.slo_window
                )
            )
        if cfg.slo_dropped_p99 >= 0 and "slo_dropped_rows" not in have:
            self.monitor.rules.append(
                health_lib.slo_dropped_rows(
                    cfg.slo_dropped_p99, window=cfg.slo_window
                )
            )
        if not cfg.burn_rate_alerts:
            return
        # burn-rate upgrades of the same SLO thresholds: fast window =
        # the SLO window, slow window = 4x — sustained low-grade burn
        # the point-in-time p99 forgives still pages (ISSUE 17)
        slow = 4 * cfg.slo_window
        if cfg.slo_latency_p99_s > 0 and "burn_rate_latency" not in have:
            self.monitor.rules.append(
                health_lib.burn_rate_latency(
                    cfg.slo_latency_p99_s,
                    fast_window=cfg.slo_window,
                    slow_window=slow,
                )
            )
        if cfg.slo_dropped_p99 >= 0 and "burn_rate_dropped" not in have:
            self.monitor.rules.append(
                health_lib.burn_rate_dropped(
                    cfg.slo_dropped_p99,
                    fast_window=cfg.slo_window,
                    slow_window=slow,
                )
            )

    def _install_store(self):
        # one JournalStore per store root; a supervisor-restarted driver
        # re-opens the same root and the manifest's drain watermark
        # (seq against the SHARED recorder) keeps drains exactly-once
        if not self.cfg.store_dir:
            return None
        from mpi_grid_redistribute_tpu.telemetry.store import JournalStore

        return JournalStore(
            self.cfg.store_dir,
            segment_events=self.cfg.store_segment_events,
            retain_bytes=self.cfg.store_retain_bytes,
            compact_after=self.cfg.store_compact_after,
        )

    def _install_flight_recorder(self):
        # idempotent per shared recorder (telemetry.incident.install):
        # a restarted driver re-registers the SAME flight recorder on
        # its fresh monitor, so debounce clocks and the bundle counter
        # survive the restart instead of re-capturing a standing alert
        if not self.cfg.incident_dir:
            return None
        from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib

        return incident_lib.install(
            self.monitor,
            self.recorder,
            self.cfg.incident_dir,
            debounce_s=self.cfg.incident_debounce_s,
        )

    def _install_rebalance_rule(self) -> None:
        # replace the stock WARN-severity imbalance_ratio rule with an
        # ALERT copy at the actuation threshold: for the closed loop the
        # finding is a trigger, not an advisory. Same shared-monitor
        # discipline as the SLO rules — a restarted driver must not
        # stack a second copy.
        from mpi_grid_redistribute_tpu.telemetry import health as health_lib

        cfg = self.cfg
        if not cfg.rebalance:
            return
        if any(
            r.name == "imbalance_ratio" and r.severity == health_lib.ALERT
            for r in self.monitor.rules
        ):
            return
        self.monitor.rules = [
            r for r in self.monitor.rules if r.name != "imbalance_ratio"
        ]
        self.monitor.rules.append(
            health_lib.imbalance_ratio(
                cfg.rebalance_threshold, severity=health_lib.ALERT
            )
        )

    # ---------------------------------------------------------- build

    @property
    def nranks(self) -> int:
        from mpi_grid_redistribute_tpu.domain import ProcessGrid

        return ProcessGrid(self.cfg.grid_shape).nranks

    def _ensure_built(self) -> None:
        if self._rd is not None:
            return
        from mpi_grid_redistribute_tpu.api import GridRedistribute
        from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid

        cfg = self.cfg
        domain = Domain(0.0, 1.0, periodic=True)
        grid = ProcessGrid(cfg.grid_shape)
        kwargs = dict(
            # capacity = n_local: the self-pair carries every resident row
            # in a drift regime, so anything smaller guarantees overflow
            capacity=cfg.n_local,
            on_overflow="grow",
            engine=self.engine,
            # re-install the live assignment-aware edges across rebuilds
            # (degrade drops _rd; the rebalance must not be undone by it)
            edges=self._edges,
        )
        if cfg.backend == "numpy":
            self._rd = GridRedistribute(
                domain, grid, backend="numpy", **kwargs
            )
        else:
            import jax

            from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

            if len(jax.devices()) >= grid.nranks:
                kwargs["mesh"] = mesh_lib.make_mesh(grid)
            # else: fewer devices than ranks — the vrank path (all
            # shards resident on one device, vmapped engine). The same
            # service loop runs on a laptop CPU as on the full mesh;
            # GridRedistribute warns when more than one device is
            # visible but only one is used.
            self._rd = GridRedistribute(domain, grid, **kwargs)
        # one journal for the whole service: the engine's own events
        # (capacity_grow, overflow windows, redistribute) land in the
        # driver's ring, next to snapshot/restore/fault/restart events
        self._rd.telemetry = self.recorder
        self._rd.monitor = self.monitor
        self._chunk_cache.clear()  # macro fns close over the old engine

    # ---------------------------------------------------------- state

    def init_state(self) -> None:
        """Fresh seeded state: rows pre-placed on their owning shard
        (slab-uniform), velocities sized for ``cfg.migration``. Every
        row gets a stable int32 id (its initial global slot index) —
        ids ride every redistribute as a passenger field, so the global
        particle SET stays identifiable across restarts AND mesh
        reshapes (the elastic bit-identity audits sort by id)."""
        from mpi_grid_redistribute_tpu.models import initial

        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        v_scale, _, _ = initial.drift_sizing(
            cfg.grid_shape, cfg.n_local, cfg.fill, cfg.migration
        )
        pos, vel, _ = initial.uniform_state(
            cfg.grid_shape, cfg.n_local, 1.0, rng, vel_scale=v_scale
        )
        ids = np.arange(self.nranks * cfg.n_local, dtype=np.int32)
        count = np.full(
            (self.nranks,), int(cfg.fill * cfg.n_local), np.int32
        )
        self.state = (pos, vel, ids, count)
        self.step = 0

    def restore_latest(self, grid_shape: Optional[Tuple[int, ...]] = None
                       ) -> bool:
        """Restore from the newest VALID snapshot (corrupt ones are
        skipped and the skip count journaled). Returns False when no
        valid snapshot exists — the caller falls back to
        :meth:`init_state`.

        Elastic (ISSUE 8): ``grid_shape`` overrides the configured mesh
        (the supervisor's shrink policy passes it), and the fault plan's
        ``device_budget`` hook may report fewer surviving devices than
        the target grid needs — the grid is then shrunk to fit
        (:func:`..parallel.mesh.shrink_to_fit`). Whenever the snapshot's
        ``(nranks, rows_per_shard)`` layout differs from the target, the
        particle pytree is re-sharded onto the new grid in ONE canonical
        redistribute (:func:`..service.elastic.reshard_state`), the
        config is rewritten to the new mesh, and a ``reshard`` event
        with old/new shapes and moved-row counts is journaled. With
        ``cfg.auto_reshard`` off, any mismatch raises
        :class:`~.elastic.ElasticRestoreError` naming both shapes
        instead of failing deep in state unflattening."""
        from mpi_grid_redistribute_tpu.service.elastic import (
            ElasticRestoreError,
        )

        cfg = self.cfg
        if not cfg.snapshot_dir:
            return False
        latest = checkpoint.load_latest(cfg.snapshot_dir)
        if latest is None:
            return False
        a = dict(latest.arrays)
        man = latest.manifest
        snap_r = int(man["nranks"])
        snap_rows = int(man["rows_per_shard"])
        snap_grid = (man.get("extra") or {}).get("grid_shape")
        snap_desc = (
            f"grid {tuple(snap_grid)}" if snap_grid
            else f"{snap_r} shards"
        ) + f" x {snap_rows} rows"
        if "ids" not in a:
            # pre-elastic snapshot: synthesize stable slot-index ids
            a["ids"] = np.arange(snap_r * snap_rows, dtype=np.int32)
        target = tuple(
            int(x) for x in (grid_shape or cfg.grid_shape)
        )
        budget = self.faults.device_budget(self)
        if budget is not None:
            from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

            fit = mesh_lib.shrink_to_fit(target, budget)
            if fit != target and not cfg.auto_reshard:
                raise ElasticRestoreError(
                    f"snapshot {latest.path!r} ({snap_desc}) needs "
                    f"{int(np.prod(target))} devices for grid {target}, "
                    f"but the mesh reports only {budget} and "
                    f"auto_reshard is disabled"
                )
            target = fit
        same_layout = (
            target == tuple(cfg.grid_shape)
            and snap_r == self.nranks
            and snap_rows == cfg.n_local
        )
        if same_layout:
            self.state = (
                np.asarray(a["pos"], np.float32),
                np.asarray(a["vel"], np.float32),
                np.asarray(a["ids"], np.int32),
                np.asarray(a["count"], np.int32),
            )
        else:
            if not cfg.auto_reshard:
                raise ElasticRestoreError(
                    f"snapshot {latest.path!r} ({snap_desc}) does not "
                    f"match the configured grid {tuple(cfg.grid_shape)} "
                    f"x {cfg.n_local} rows and auto_reshard is disabled"
                )
            from mpi_grid_redistribute_tpu.service.elastic import (
                reshard_state,
            )

            res = reshard_state(a, man, target)
            self.cfg = cfg = dataclasses.replace(
                cfg, grid_shape=target, n_local=res.n_local
            )
            self._rd = None  # rebuilt on the new mesh at the next step
            out = res.arrays
            self.state = (
                np.asarray(out["pos"], np.float32),
                np.asarray(out["vel"], np.float32),
                np.asarray(out["ids"], np.int32),
                np.asarray(out["count"], np.int32),
            )
            self.recorder.record(
                "reshard",
                old_grid=list(snap_grid) if snap_grid else None,
                old_shards=snap_r,
                old_rows_per_shard=snap_rows,
                new_grid=list(target),
                new_rows_per_shard=res.n_local,
                rows=res.live_rows,
                moved=res.moved_rows,
                step=int(man["step"]),
                path=latest.path,
            )
        self.step = int(man["step"])
        self.recorder.record(
            "restore",
            what="state",
            step=self.step,
            path=latest.path,
            snapshots_skipped=latest.skipped,
        )
        return True

    # ------------------------------------------------------ snapshots

    def join_snapshot_writer(self) -> None:
        """Block until the in-flight async snapshot write (if any) has
        committed; re-raise its failure — a write error must surface as
        a driver failure, never vanish into the thread."""
        t = self._writer
        if t is not None:
            t.join()
            self._writer = None
        # swap-and-clear under the lock so the error surfaces exactly
        # once: close() after a failed snapshot (or abandon() after
        # close() already raised) must not re-raise the same write error
        with self._writer_lock:
            err, self._writer_error = self._writer_error, None
        if err is not None:
            raise RuntimeError(f"async snapshot write failed: {err}")

    def snapshot(self) -> str:
        """Write one snapshot of the full particle pytree; journal it."""
        cfg = self.cfg
        pos, vel, ids, count = self.state
        step = self.step
        path = os.path.join(cfg.snapshot_dir, f"step_{step:08d}")
        # the state tuple is never mutated in place (_advance returns
        # fresh arrays), so the writer thread can serialize these exact
        # arrays without a defensive copy
        arrays = {"pos": pos, "vel": vel, "ids": ids, "count": count}
        extra = {
            "seed": cfg.seed,
            "engine": self.engine,
            "grid_shape": list(cfg.grid_shape),
        }

        # thread-locals don't cross the spawn: hand the writer a child
        # of the loop's context so anything it journals (or an incident
        # capture racing it) attributes to the step being snapshotted
        ctx = context_lib.current()
        wctx = (
            ctx.child(step=step, origin="snapshot-writer")
            if ctx is not None
            else None
        )

        def write() -> None:
            with context_lib.use(wctx), span("host:snapshot_write"):
                try:
                    checkpoint.save(
                        path, arrays, nranks=self.nranks, step=step,
                        extra=extra,
                    )
                except Exception as e:  # surfaced by join_snapshot_writer
                    with self._writer_lock:
                        self._writer_error = f"{type(e).__name__}: {e}"

        self.join_snapshot_writer()  # at most one write in flight
        cadence_s = float(cfg.snapshot_every) * float(self._wall_ema or 0.0)
        self.recorder.record(
            "snapshot",
            step=step,
            path=path,
            cadence_s=cadence_s,
            rows=int(count.sum()),
            asynchronous=bool(cfg.snapshot_async),
        )
        if cfg.snapshot_async:
            t = threading.Thread(target=write, daemon=True)
            self._writer = t
            t.start()
        else:
            write()
            self.join_snapshot_writer()
        self._last_snapshot_path = path
        self._prune_snapshots()
        self.export_journal()
        return path

    def _prune_snapshots(self) -> None:
        keep = self.cfg.keep_snapshots
        for path in checkpoint.list_snapshots(self.cfg.snapshot_dir)[keep:]:
            if path == self._last_snapshot_path:
                continue  # never the one just written (possibly in flight)
            import shutil

            shutil.rmtree(path)

    def export_journal(self) -> Optional[str]:
        """Export the retained journal window as this process's shard.

        A previously exported shard that has vanished (disk fault,
        operator error — :class:`~.faults.JournalShardLossFault`) is
        detected here and healed by re-exporting the retained window,
        with a journaled ``restore`` event so the loss is auditable."""
        cfg = self.cfg
        if not cfg.journal_dir:
            return None
        os.makedirs(cfg.journal_dir, exist_ok=True)
        rec = self.recorder
        path = os.path.join(
            cfg.journal_dir, f"driver.{rec.host}.{rec.pid}.jsonl"
        )
        if self.journal_path is not None and not os.path.exists(
            self.journal_path
        ):
            rec.record("restore", what="journal", path=self.journal_path)
        rec.to_jsonl(path)
        self.journal_path = path
        return path

    # ------------------------------------------------------------ run

    def _advance(self, pos, vel, ids, count):
        cfg = self.cfg
        one = np.float32(1.0)
        pos = (pos + vel * np.float32(cfg.dt)) % one
        # float32 `%` can round a tiny negative up to exactly 1.0, which
        # is outside the periodic domain [0, 1)
        pos = np.where(pos >= one, pos - one, pos)
        res = self._rd.redistribute(pos, vel, ids, count=count)
        st = res.stats
        self._last_dropped = 0 if st is None else (
            int(np.asarray(st.dropped_send).sum())
            + int(np.asarray(st.dropped_recv).sum())
        )
        return (
            np.asarray(res.positions),
            np.asarray(res.fields[0]),
            np.asarray(res.fields[1], np.int32),
            np.asarray(res.count, np.int32),
        )

    def _refresh_flow(self) -> None:
        # fold the latest redistribute stats into the flow gauge and
        # journal a flow_snapshot, so the imbalance_ratio rule sees the
        # CURRENT decomposition (gated on cfg.rebalance in the caller:
        # non-rebalancing services keep their journal shape unchanged)
        if self._rd is not None and self._rd._last_stats is not None:
            self._rd.flow(update=True)

    def _health_check(self) -> dict:
        from mpi_grid_redistribute_tpu.service.faults import SLOBreachError

        if self.cfg.rebalance:
            self._refresh_flow()
        verdict = self.monitor.evaluate()
        if not self.degraded and self.engine != "planar":
            for f in verdict["findings"]:
                if f["rule"] == "fast_path_fallback":
                    self._degrade(f["reason"])
                    break
        if self.cfg.rebalance:
            # actuate BEFORE the slo_ raise loop: a rebalance that fixes
            # the hot rank this boundary must not be pre-empted by a
            # restart the imbalance itself provoked. Any configured
            # trigger rule (population skew OR backlog growth) may fire
            # the same plan->guard->apply pipeline; the `rebalance`
            # event journals which one did.
            trigger_on = set(self.cfg.rebalance_on)
            for f in verdict["findings"]:
                if f["rule"] in trigger_on and f["severity"] == "ALERT":
                    self._maybe_rebalance(f)
                    break
        for f in verdict["findings"]:
            # an SLO breach is a FAILURE, not an advisory: raise out of
            # the loop so the supervisor restarts (and shrinks on repeat)
            if f["rule"].startswith("slo_"):
                raise SLOBreachError(f"{f['rule']}: {f['reason']}")
        return verdict

    def _maybe_rebalance(self, finding: dict) -> None:
        """ALERT -> plan -> guard -> (maybe) one-shot apply_assignment.

        Journals a ``rebalance`` event on EVERY path — applied or
        declined — so the closed loop is auditable from the journal
        alone (telemetry/SCHEMA.md)."""
        from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
        from mpi_grid_redistribute_tpu.telemetry import flow as flow_lib
        from mpi_grid_redistribute_tpu.telemetry import rebalance as reb_lib

        cfg = self.cfg
        if self._planner is None:
            self._planner = reb_lib.RebalancePlanner(
                Domain(0.0, 1.0, periodic=True),
                ProcessGrid(cfg.grid_shape),
                cells_per_rank_axis=cfg.rebalance_cells,
            )
        if self._guard is None:
            self._guard = reb_lib.AmortizationGuard(
                horizon_steps=cfg.rebalance_horizon,
                cooldown_steps=cfg.rebalance_cooldown,
                min_improvement=cfg.rebalance_min_improvement,
            )
        pos, vel, ids, count = self.state
        plan = self._planner.plan(pos, count=count)
        if plan is None:
            self.recorder.record(
                "rebalance",
                step=self.step,
                applied=False,
                reason="no live rows to balance",
                rule=finding["rule"],
                trigger=finding["reason"],
            )
            return
        step_s = float(self._wall_ema or 0.0)
        d = self._guard.consider(
            step=self.step,
            step_seconds=step_s,
            old_imbalance=plan.old_imbalance,
            projected_imbalance=plan.projected_imbalance,
        )
        if not d.apply:
            self.recorder.record(
                "rebalance",
                step=self.step,
                applied=False,
                reason=d.reason,
                rule=finding["rule"],
                trigger=finding["reason"],
                old_imbalance=plan.old_imbalance,
                projected_imbalance=plan.projected_imbalance,
                projected_saving_s=d.projected_saving_s,
                cost_s=d.cost_s,
            )
            return
        t0 = time.perf_counter()
        res = self._rd.apply_assignment(plan.edges, pos, vel, ids,
                                        count=count)
        self.state = (
            np.asarray(res.positions),
            np.asarray(res.fields[0]),
            np.asarray(res.fields[1], np.int32),
            np.asarray(res.count, np.int32),
        )
        cost = time.perf_counter() - t0
        self._edges = plan.edges  # survives _rd rebuilds (_ensure_built)
        m = flow_lib.flow_matrix_of(res.stats)[-1]
        rows_moved = int(m.sum() - np.trace(m))
        new_counts = np.asarray(self.state[3], np.float64)
        realized = (
            float(new_counts.max() / new_counts.mean())
            if new_counts.mean() > 0 else 1.0
        )
        realized_saving_s = (
            step_s * (1.0 - realized / plan.old_imbalance)
            if plan.old_imbalance > 0 else 0.0
        )
        self._guard.note_applied(self.step, cost)
        self.recorder.record(
            "rebalance",
            step=self.step,
            applied=True,
            reason=d.reason,
            rule=finding["rule"],
            trigger=finding["reason"],
            old_imbalance=plan.old_imbalance,
            projected_imbalance=plan.projected_imbalance,
            realized_imbalance=realized,
            rows_moved=rows_moved,
            projected_saving_s=d.projected_saving_s,
            realized_saving_s=realized_saving_s,
            cost_s=cost,
            n_cells=plan.n_cells,
            occupied_cells=plan.occupied_cells,
        )
        # refresh the gauge from the post-apply stats: the stale
        # pre-rebalance snapshot must not re-fire the ALERT next boundary
        self._refresh_flow()

    def _degrade(self, reason: str) -> None:
        self.recorder.record(
            "degrade",
            **{"from": self.engine, "to": "planar", "reason": reason},
        )
        self.engine = "planar"
        self.degraded = True
        self._rd = None  # rebuilt with the pinned engine on next step

    def snapshots_corrupt(self) -> int:
        """Corrupt snapshots skipped over by restores, summed from the
        retained ``restore`` events — the journal twin of the
        ``grid_snapshot_corrupt_total`` counter the metrics plane
        scrapes (it used to be counted by ``load_latest`` and then
        dropped on the floor)."""
        return sum(
            int(e.data.get("snapshots_skipped", 0) or 0)
            for e in self.recorder.events("restore")
            if e.data.get("what") == "state"
        )

    def healthz(self) -> Tuple[int, dict]:
        """The ``/healthz`` contract for the supervisor: read-only rule
        evaluation, HTTP-style status code (503 on ALERT). The verdict
        carries ``snapshots_corrupt`` so a poller sees skipped-over
        corruption without scraping the metrics plane."""
        verdict = self.monitor.evaluate(record=False)
        verdict["snapshots_corrupt"] = self.snapshots_corrupt()
        return (503 if verdict["status"] == "ALERT" else 200), verdict

    # -------------------------------------------- chunked run machinery

    def _chunk_len_from(self, step: int, end: int) -> int:
        """Steps the next chunk may advance from ``step``: ``cfg.chunk``
        clipped to the horizon and auto-split at the next scheduled
        snapshot/health boundary and the next fault-eligible step
        (``FaultPlan.next_step``), so every boundary lands exactly where
        the eager loop would put it. A fault eligible at ``step`` itself
        forces a singleton chunk — the fault then fires (and is timed,
        watchdogged, journaled) exactly as in the eager loop."""
        cfg = self.cfg
        n = min(max(1, int(cfg.chunk)), end - step)
        if n > 1:
            for every in (cfg.snapshot_every, cfg.health_every):
                if every:
                    n = min(n, every - step % every)
        if n > 1 and self.faults:
            nf = self.faults.next_step(step)
            if nf is not None:
                n = min(n, max(1, nf - step))
        return max(1, n)

    def _boundary_free(self, step: int) -> bool:
        # True when completing `step` triggers no snapshot/health work
        # and no fault is eligible there — the precondition for
        # dispatching the chunk that starts at `step` before retiring
        # its predecessor (async overlap)
        cfg = self.cfg
        if cfg.snapshot_every and step % cfg.snapshot_every == 0:
            return False
        if cfg.health_every and step % cfg.health_every == 0:
            return False
        if self.faults:
            nf = self.faults.next_step(step)
            if nf is not None and nf <= step:
                return False
        return True

    def _resident_ok(self) -> bool:
        # the scan carry needs out_capacity == n_local; a recv-side
        # capacity grow breaks that invariant and pins the driver to the
        # eager per-step loop (which handles ragged capacities)
        rd = self._rd
        return rd is not None and (
            rd.out_capacity is None
            or int(rd.out_capacity) == int(self.cfg.n_local)
        )

    def _macro_fn(self, n: int):
        """Compiled ``n``-step macro fn (+ its capacities), cached on
        everything that changes the traced program."""
        from mpi_grid_redistribute_tpu.service import pipeline, resident

        rd = self._rd
        pos, vel, ids, _ = self.state
        pipelined = bool(self.cfg.pipeline) and n >= 2
        key = (
            n, pos.shape[0], rd.capacity, rd.out_capacity,
            rd._mover_cap, rd.edges, self.engine, pipelined,
            self._probes,
        )
        entry = self._chunk_cache.get(key)
        if entry is None:
            build = (
                pipeline.make_pipelined_chunk_fn
                if pipelined
                else resident.make_chunk_fn
            )
            entry = build(
                rd, self.cfg.dt, n, pos, vel, ids, probes=self._probes
            )
            self._chunk_cache[key] = entry
        return entry

    def _materialize_state(self) -> None:
        # device carry -> host numpy, at chunk boundaries that need the
        # bytes (snapshot/rebalance/run-exit); jax arrays are immutable,
        # so a pre-dispatched next chunk keeps computing unaffected
        st = self.state
        if st is not None and not isinstance(st[0], np.ndarray):
            self.state = (
                np.asarray(st[0]),
                np.asarray(st[1]),
                np.asarray(st[2], np.int32),
                np.asarray(st[3], np.int32),
            )

    def _finish_steps(self, n, compute_s, budget_s, dropped) -> None:
        """Fold one completed chunk into the per-step surfaces: n
        ``step_latency`` events (wall apportioned from the chunk,
        dropped from the ys), the monitor's step-time samples, the
        snapshot-cadence EMA, and the watchdog (chunk budget / chunk
        length). ``cfg.step_sleep`` is excluded from ``compute_s`` (the
        SLO/EMA wall) but included in ``budget_s`` (the watchdog's) —
        pacing is not latency, but a stalled sleep is still a stall."""
        from mpi_grid_redistribute_tpu import telemetry as telemetry_lib

        cfg = self.cfg
        per = compute_s / n
        first = self.step + 1
        self.step += n
        for _ in range(n):
            self.monitor.note_step_time(per)
        telemetry_lib.record_chunk_steps(self.recorder, first, per, dropped)
        self._last_dropped = int(dropped[-1])
        for _ in range(n):
            self._wall_ema = (
                per if self._wall_ema is None
                else 0.2 * per + 0.8 * self._wall_ema
            )
        per_budget = budget_s / n
        if cfg.watchdog_s and per_budget > cfg.watchdog_s:
            raise StallError(
                f"step {self.step} took {per_budget:.3f}s "
                f"(> {cfg.watchdog_s:.3f}s watchdog)"
            )

    def _note_probe_steps(self, probe) -> None:
        """Journal one ``state_health`` event per probed step (from
        already-fetched host arrays) and latch the breach flag when any
        corruption counter is nonzero. The latch — not the raw events —
        is what :meth:`_state_health_gate` consumes, so the steady-state
        per-boundary cost of an armed probe is a few comparisons on
        chunk-length arrays, never a full rule evaluation."""
        record_probe_steps(self.recorder, self.step + 1, probe)
        for k in ("nan_pos", "nan_vel", "oob", "residual"):
            if np.asarray(probe[k]).any():
                self._state_breach = True
                break

    def _state_health_gate(self) -> None:
        # corruption fails the boundary BEFORE the snapshot hook: a
        # snapshot taken now would freeze the corrupt state, and the
        # supervisor's restore would then faithfully bring the damage
        # back. Raising first keeps the newest snapshot pre-corruption.
        if not self._state_breach:
            return
        from mpi_grid_redistribute_tpu.service.faults import (
            _STATE_RULES,
            StateCorruptionError,
        )

        self._state_breach = False
        # evaluate() journals the nan_detected / conservation_drift /
        # bounds_violation ALERT and fires the flight recorder callback,
        # so the incident bundle freezes before the raise tears us down
        verdict = self.monitor.evaluate()
        reasons = [
            f"{f['rule']}: {f['reason']}"
            for f in verdict["findings"]
            if f["rule"] in _STATE_RULES
        ]
        raise StateCorruptionError(
            "; ".join(reasons)
            or "state_health breach (events evicted before the gate)"
        )

    def _run_boundary(self) -> None:
        # snapshot/health hooks, on the step the chunk just ended at;
        # _chunk_len_from guarantees chunks never straddle a boundary
        cfg = self.cfg
        # freeze fault bundles BEFORE the health pass: a health finding
        # the fault provoked may raise (SLOBreachError) out of the check
        if self._flight is not None:
            self._flight.scan_faults()
        try:
            self._state_health_gate()
            if cfg.snapshot_every and self.step % cfg.snapshot_every == 0:
                # the state's device-to-host copy and the write's enqueue
                with span("host:snapshot"):
                    self._materialize_state()
                    path = self.snapshot()
                self.faults.after_snapshot(self, path)
                self._health_check()
            elif cfg.health_every and self.step % cfg.health_every == 0:
                self._materialize_state()
                self._health_check()
        finally:
            # drain the ring into the durable store AFTER the health
            # pass (its alert events make this boundary's segment) and
            # even when the check raised SLOBreachError — the breach
            # evidence must be on disk before the restart tears us down
            if self._store is not None:
                with span("host:journal_drain"):
                    self._store.drain(self.recorder)

    def _run_chunk_eager(self, n: int, fire_faults: bool = True) -> None:
        """Advance ``n`` steps through the eager per-step engine path
        (``n=1`` is exactly the pre-chunking loop). Used for the numpy
        oracle backend at any chunk length, for singleton chunks (fault
        steps, chunk=1 configs), and as the self-healing fallback when a
        resident chunk overflowed."""
        cfg = self.cfg
        t0 = time.perf_counter()
        if fire_faults:
            self.faults.before_step(self)
        self._materialize_state()
        armed = self._probes.armed
        if armed:
            # per-chunk conservation ledger, same anchoring as the
            # resident scan: initial live rows at chunk entry, dropped
            # rows accumulated per step — so a step executed eagerly
            # (fault chunk, overflow re-run, numpy backend) journals
            # counter-exact state_health events
            live0 = int(np.asarray(self.state[3]).sum())
            cum = 0
        dropped = []
        for i in range(n):
            self.state = self._advance(*self.state)
            dropped.append(self._last_dropped)
            if armed:
                cum += self._last_dropped
                pos, vel, _, count = self.state
                payload = summarize_host(
                    pos, vel, count, live0, cum, self._probes
                )
                self.recorder.record(
                    "state_health", step=self.step + 1 + i, **payload
                )
                if (
                    payload["nan_pos"] or payload["nan_vel"]
                    or payload["oob"] or payload["residual"]
                ):
                    self._state_breach = True
        compute = time.perf_counter() - t0
        if cfg.step_sleep:
            time.sleep(cfg.step_sleep * n)
        budget = time.perf_counter() - t0
        self._finish_steps(n, compute, budget, dropped)
        self._run_boundary()

    def _dispatch_chunk(self, n: int):
        """Dispatch one resident macro-step (jax async dispatch: returns
        immediately with futures for the carry and the ys)."""
        self.faults.before_step(self)  # no-op by construction: any
        # eligible injector forced a singleton chunk via _chunk_len_from
        t0 = time.perf_counter()
        self._ensure_built()
        macro, cap, out_cap = self._macro_fn(n)
        entry = self.state
        carry, ys = macro(*entry)
        return (n, t0, cap, out_cap, entry, carry, ys)

    def _retire_chunk(self, pending, end: int):
        """Block on a dispatched chunk's (tiny) ys, fold them into the
        per-step surfaces, and run the boundary hooks. When the NEXT
        chunk has no boundary work at its start, it is dispatched from
        the in-flight carry BEFORE this chunk's host reads — journal,
        metrics and snapshot serialization then overlap device compute.
        Returns the pre-dispatched pending chunk (or None)."""
        from mpi_grid_redistribute_tpu.service import resident

        cfg = self.cfg
        n, t0, cap, out_cap, entry, carry, ys = pending
        step_after = self.step + n
        nxt = None
        if step_after < end and self._boundary_free(step_after):
            n2 = self._chunk_len_from(step_after, end)
            if n2 > 1:
                t0b = time.perf_counter()
                macro2, cap2, out2 = self._macro_fn(n2)
                carry2, ys2 = macro2(*carry)
                nxt = (n2, t0b, cap2, out2, carry, carry2, ys2)
        # host sync point: materialize the per-step stats (tiny arrays)
        stats = ys["stats"]
        ds = np.asarray(stats.dropped_send)    # [n, R]
        dr = np.asarray(stats.dropped_recv)    # [n, R]
        now = time.perf_counter()
        anchor = t0 if self._chunk_done is None else max(
            t0, self._chunk_done
        )
        compute = now - anchor
        if ds.any() or dr.any():
            # overflow inside the chunk: the scanned steps ran at too
            # small a capacity. Grow from the measured need, drop the
            # chunk (and any pre-dispatched successor — it consumed the
            # lossy carry), and re-run these n steps through the eager
            # path, which heals exactly like redistribute() does.
            counts = np.asarray(ys["count"])
            needed = int(np.asarray(stats.needed_capacity).max())
            needed_out = int((counts + dr).max())
            self._rd._grow(
                int(ds.sum()), int(dr.sum()), needed, needed_out,
                int(self.cfg.n_local), cap, out_cap,
            )
            self._chunk_cache.clear()
            self.state = entry
            self._run_chunk_eager(n, fire_faults=False)
            self._chunk_done = time.perf_counter()
            return None
        if cfg.step_sleep:
            time.sleep(cfg.step_sleep * n)
        budget = time.perf_counter() - anchor
        self.state = carry
        self._rd._last_stats = resident.final_stats(stats)
        # per-step engine surface: the same `redistribute` journal event
        # stream the eager loop emits (static per chunk: one resolved
        # engine, one wire model)
        rd = self._rd
        wire = rd._last_wire or {}
        wire_bytes = (
            wire.get("engine_cols", 0)
            * (rd._last_row_bytes or 0)
            * wire.get("shards", 0)
        )
        for _ in range(n):
            rd._call_index += 1
            self.recorder.record(
                "redistribute",
                call=rd._call_index,
                n_local=int(cfg.n_local),
                capacity=cap,
                out_capacity=out_cap,
                engine=wire.get("engine", self.engine),
                wire_bytes=wire_bytes,
            )
        probe = ys.get("probe")
        if probe is not None:
            # tiny host reads, same transfer contract as the stats ys
            self._note_probe_steps(
                {k: np.asarray(v) for k, v in probe.items()}
            )
        dropped = (ds.sum(axis=1) + dr.sum(axis=1)).tolist()
        self._finish_steps(n, compute, budget, dropped)
        self._chunk_done = time.perf_counter()
        self._run_boundary()
        return nxt

    def run(self, max_steps: Optional[int] = None):
        """Advance up to ``max_steps`` (default: to ``cfg.steps``).

        With ``cfg.chunk > 1`` on the jax backend the loop is resident:
        each iteration dispatches one ``chunk``-step ``lax.scan`` macro
        step (``service/resident.py``) and folds its scanned ys into
        the per-step journal/SLO/health surfaces at the chunk boundary;
        chunk k+1 is dispatched before blocking on chunk k's host reads
        whenever no boundary work separates them. ``chunk=1`` (and the
        numpy backend's per-step engine) reproduce the eager loop
        bit-for-bit — including the final particle set for ANY chunk,
        which the fault-matrix tests audit via
        ``elastic.particle_set``."""
        cfg = self.cfg
        if self.state is None:
            self.init_state()
        end = cfg.steps
        if max_steps is not None:
            end = min(end, self.step + int(max_steps))
        pending = None
        # one profiler trace per run() call when cfg.profile_dir or
        # GRID_PROFILE_DIR is set; a no-op otherwise (ISSUE 14)
        session = ProfilerSession(
            cfg.profile_dir,
            recorder=self.recorder,
            label=f"run@{self.step}",
        )
        # causal step context (telemetry/context.py): inherit the
        # supervisor's per-attempt context when one is active (so the
        # trace id spans restarts and ctx_attempt rides along), else
        # open a deterministic root trace derived from the config seed.
        # Each loop iteration re-scopes to the chunk's first step, so
        # every event it journals (redistribute, step_latency, snapshot,
        # alert, fault_injected) carries ctx_step in its envelope.
        cur = context_lib.current()
        root = (
            cur.child(origin="driver")
            if cur is not None
            else context_lib.StepContext(
                trace=f"svc-{cfg.seed:08x}", origin="driver"
            )
        )
        try:
            with context_lib.use(root), session:
                while self.step < end:
                    with context_lib.scoped(step=self.step + 1):
                        self._ensure_built()
                        if pending is not None:
                            pending = self._retire_chunk(pending, end)
                            continue
                        n = self._chunk_len_from(self.step, end)
                        if (
                            n == 1
                            or cfg.backend != "jax"
                            or not self._resident_ok()
                        ):
                            self._run_chunk_eager(n)
                            continue
                        pending = self._dispatch_chunk(n)
        finally:
            self._materialize_state()
        return self.state

    def close(self) -> None:
        """Orderly shutdown: commit the in-flight snapshot, resolve the
        engine's deferred overflow windows, export the final journal."""
        self.join_snapshot_writer()
        if self._rd is not None:
            self._rd.flush_overflow_checks()
        if self._flight is not None:
            # a fault that crashed the attempt before the next boundary
            # still leaves its incident bundle behind
            self._flight.scan_faults()
        if self._store is not None:
            # final drain + rotate/compact/retention BEFORE the journal
            # export, so the exported shard includes the last store_drain
            self._store.close(self.recorder)
        self.export_journal()

    def abandon(self) -> Optional[str]:
        """Failure-path teardown: like :meth:`close`, but returns any
        secondary error as a string for the supervisor to append to the
        primary failure instead of raising over it."""
        try:
            self.close()
        except Exception as e:
            return f"teardown after failure also failed: " \
                   f"{type(e).__name__}: {e}"
        return None


# ------------------------------------------------------------------ CLI


def main(argv=None) -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(
        prog="service.driver",
        description="long-running drift->redistribute service loop",
    )
    p.add_argument("--grid", default="2,2,2")
    p.add_argument("--n-local", type=int, default=4096)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--fill", type=float, default=0.9)
    p.add_argument("--migration", type=float, default=0.02)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--backend", default="jax", choices=("jax", "numpy"))
    p.add_argument("--engine", default="auto")
    p.add_argument("--snapshot-every", type=int, default=0)
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--journal-dir", default=None)
    p.add_argument(
        "--store-dir", default=None, metavar="DIR",
        help="durable journal store root (telemetry/store.py): the "
             "recorder ring is drained here at every chunk/health "
             "boundary; watch with scripts/grid_top.py --store DIR",
    )
    p.add_argument("--keep-snapshots", type=int, default=4)
    p.add_argument("--sync-snapshots", action="store_true")
    p.add_argument("--watchdog", type=float, default=0.0)
    p.add_argument("--step-sleep", type=float, default=0.0)
    p.add_argument(
        "--chunk", type=int, default=1,
        help="steps per resident macro-dispatch (lax.scan; jax backend; "
             "1 = eager per-step loop)",
    )
    p.add_argument(
        "--pipeline", action="store_true",
        help="software-pipeline the resident macro-step: overlap each "
             "step's exchange with the next step's binning "
             "(service/pipeline.py; degrades to the sequential body "
             "when the schedule is infeasible)",
    )
    p.add_argument(
        "--probes", default="off", choices=("off", "counters", "moments"),
        help="in-graph state-health probe tier (telemetry/probes.py): "
             "journal per-step state_health events and fail the chunk "
             "boundary on NaN / out-of-bounds / conservation drift "
             "(off = bit-identical unprobed program)",
    )
    p.add_argument(
        "--no-resume", action="store_true",
        help="ignore existing snapshots; start from the seeded state",
    )
    p.add_argument(
        "--supervise", action="store_true",
        help="run under the Supervisor (restore/backoff/circuit breaker)",
    )
    p.add_argument("--max-restarts", type=int, default=5)
    p.add_argument("--window-s", type=float, default=300.0)
    p.add_argument("--backoff-base", type=float, default=0.05)
    p.add_argument("--backoff-cap", type=float, default=2.0)
    p.add_argument(
        "--slo-p99", type=float, default=0.0, metavar="SECONDS",
        help="p99 step-latency SLO; sustained breach restarts (0 = off)",
    )
    p.add_argument(
        "--no-reshard", action="store_true",
        help="disable elastic restore (mesh-mismatched snapshots error)",
    )
    p.add_argument(
        "--rebalance", action="store_true",
        help="close the loop: imbalance_ratio ALERT -> plan -> "
             "amortization guard -> one-shot apply_assignment",
    )
    p.add_argument(
        "--rebalance-threshold", type=float, default=2.0,
        help="imbalance ratio (max/mean) that trips the ALERT",
    )
    p.add_argument(
        "--rebalance-cells", type=int, default=2,
        help="fine planning cells per grid cell per axis",
    )
    p.add_argument(
        "--rebalance-horizon", type=int, default=256,
        help="steps the projected saving may amortize the apply cost over",
    )
    p.add_argument(
        "--rebalance-cooldown", type=int, default=64,
        help="minimum steps between applied remaps",
    )
    p.add_argument(
        "--shrink-after", type=int, default=0, metavar="N",
        help="supervise mode: shrink the mesh after N consecutive "
             "SLO-breach restarts (0 = never)",
    )
    p.add_argument(
        "--inject-crash", type=int, default=None, metavar="STEP",
        help="inject a crash at STEP (-1 = every run: crash-loop)",
    )
    p.add_argument(
        "--hard-crash", action="store_true",
        help="crash via os._exit (subprocess kill tests) instead of raise",
    )
    p.add_argument(
        "--profile-dir", default=None, metavar="DIR",
        help="capture a jax.profiler trace of each run() into DIR "
             "(telemetry.profiler.ProfilerSession; GRID_PROFILE_DIR is "
             "the env spelling; journaled as profile_session events)",
    )
    p.add_argument(
        "--incident-dir", default=None, metavar="DIR",
        help="freeze a debounced incident bundle into DIR on every "
             "ALERT / injected fault (telemetry.incident.FlightRecorder; "
             "inspect with scripts/incident.py)",
    )
    p.add_argument(
        "--final-out", default=None,
        help="write the final state (pos/vel/count/step npz) here",
    )
    args = p.parse_args(argv)

    if args.backend == "jax":
        from mpi_grid_redistribute_tpu.utils import compile_cache

        compile_cache.enable()

    cfg = DriverConfig(
        grid_shape=tuple(int(x) for x in args.grid.split(",")),
        n_local=args.n_local,
        fill=args.fill,
        steps=args.steps,
        seed=args.seed,
        migration=args.migration,
        backend=args.backend,
        engine=args.engine,
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir,
        keep_snapshots=args.keep_snapshots,
        snapshot_async=not args.sync_snapshots,
        journal_dir=args.journal_dir,
        store_dir=args.store_dir,
        watchdog_s=args.watchdog,
        step_sleep=args.step_sleep,
        chunk=args.chunk,
        pipeline=args.pipeline,
        probes=args.probes,
        auto_reshard=not args.no_reshard,
        slo_latency_p99_s=args.slo_p99,
        rebalance=args.rebalance,
        rebalance_threshold=args.rebalance_threshold,
        rebalance_cells=args.rebalance_cells,
        rebalance_horizon=args.rebalance_horizon,
        rebalance_cooldown=args.rebalance_cooldown,
        profile_dir=args.profile_dir,
        incident_dir=args.incident_dir,
    )
    faults = FaultPlan()
    if args.inject_crash is not None:
        from mpi_grid_redistribute_tpu.service.faults import CrashFault

        step = None if args.inject_crash < 0 else args.inject_crash
        faults.faults.append(CrashFault(step, hard=args.hard_crash))

    if args.supervise:
        from mpi_grid_redistribute_tpu.service.supervisor import (
            RestartPolicy,
            Supervisor,
        )

        recorder = StepRecorder()

        def factory(grid_shape=None):
            c = cfg
            if grid_shape is not None:
                c = dataclasses.replace(c, grid_shape=tuple(grid_shape))
            return ServiceDriver(c, recorder=recorder, faults=faults)

        sup = Supervisor(
            factory,
            policy=RestartPolicy(
                max_restarts=args.max_restarts,
                window_s=args.window_s,
                backoff_base_s=args.backoff_base,
                backoff_cap_s=args.backoff_cap,
                shrink_after=args.shrink_after,
            ),
            recorder=recorder,
        )
        verdict = sup.run()
        print(json.dumps(verdict._asdict()), flush=True)
        if args.final_out and sup.driver is not None and (
            sup.driver.state is not None
        ):
            pos, vel, ids, count = sup.driver.state
            np.savez(
                args.final_out, pos=pos, vel=vel, ids=ids, count=count,
                step=sup.driver.step,
            )
        return 0 if verdict.ok else 3

    drv = ServiceDriver(cfg, faults=faults)
    if not args.no_resume:
        drv.restore_latest()
    drv.run()
    drv.close()
    if args.final_out:
        pos, vel, ids, count = drv.state
        np.savez(
            args.final_out, pos=pos, vel=vel, ids=ids, count=count,
            step=drv.step,
        )
    print(
        json.dumps(
            {"ok": True, "step": drv.step,
             "counts": drv.recorder.counts()}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
