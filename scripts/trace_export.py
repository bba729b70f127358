#!/usr/bin/env python
"""Export telemetry to a Perfetto/Chrome-trace JSON (`make observe`).

Thin CLI over :mod:`mpi_grid_redistribute_tpu.telemetry.traceview`.
Two input sources:

* ``--journal FILE`` — a JSON Lines journal written by
  ``StepRecorder.to_jsonl`` (or ``GridRedistribute.telemetry``); events
  are re-hydrated and become the instant + counter tracks.
* ``--demo`` — no artifacts handy: run a small in-process drift loop on
  whatever devices exist and trace that journal.

Examples:

  # journal from a run -> trace
  python scripts/trace_export.py --journal run.jsonl --out trace.json

  # self-contained demo
  python scripts/trace_export.py --demo --out trace.json

Open the output at https://ui.perfetto.dev or chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def load_journal(path: str):
    """Re-hydrate a StepRecorder from a ``to_jsonl`` export."""
    from mpi_grid_redistribute_tpu import telemetry

    rec = telemetry.StepRecorder()
    n_lines = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            kind = obj.pop("kind")
            obj.pop("seq", None)
            t = obj.pop("time", None)
            # envelope tags (ISSUE 5 multi-host shards) identify the
            # writer, not the event — keep the rehydrated payload clean
            # and carry the identity on the recorder itself
            host, pid = obj.pop("host", None), obj.pop("pid", None)
            if host is not None:
                rec.host = str(host)
            if pid is not None:
                rec.pid = int(pid)
            # record_at keeps the original wall time so track
            # timestamps are honest (record() would stamp "now")
            rec.record_at(kind, t, **obj)
            n_lines += 1
    if n_lines == 0:
        raise SystemExit(f"{path}: empty journal")
    return rec


def demo_recorder(steps: int = 16):
    """Run a small drift loop and return its populated journal."""
    import numpy as np

    from mpi_grid_redistribute_tpu import telemetry
    from mpi_grid_redistribute_tpu.models import initial
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.domain import Domain

    grid_shape = (2, 2, 2)
    dev_grid, vgrid, mesh, _ = initial.pick_layout(grid_shape)
    rng = np.random.default_rng(0)
    n_local = 1 << 11
    pos, _, alive = initial.uniform_state(grid_shape, n_local, 0.9, rng)
    vel = (0.02 * (rng.random(pos.shape, dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    cfg = nbody.DriftConfig(
        domain=Domain(0.0, 1.0, periodic=True), grid=dev_grid, dt=1.0,
        capacity=max(64, n_local // 4), n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, steps, vgrid=vgrid)
    _, _, _, st = loop(
        nbody.rows_to_planar(pos, mesh.size),
        nbody.rows_to_planar(vel, mesh.size),
        alive,
    )
    rec = telemetry.StepRecorder()
    telemetry.record_migrate_steps(rec, st, rank_totals=True)
    acc = telemetry.FlowAccumulator()
    acc.update(st)
    telemetry.record_flow_snapshot(rec, acc)
    telemetry.HealthMonitor(rec).evaluate()
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--journal", type=str, default=None,
                    help="StepRecorder JSONL export to re-hydrate")
    ap.add_argument("--demo", action="store_true",
                    help="run a small drift loop in-process and trace it")
    ap.add_argument("--steps", type=int, default=16,
                    help="demo drift steps (default 16)")
    ap.add_argument("--step-seconds", type=float, default=None,
                    help="measured per-step seconds for the counter "
                         "track's synthetic time axis (default 1 ms)")
    ap.add_argument("--out", type=str, required=True,
                    help="output trace JSON path")
    args = ap.parse_args(argv)

    if not (args.journal or args.demo):
        ap.error("nothing to export: give --journal or --demo")

    from mpi_grid_redistribute_tpu.telemetry import traceview

    rec = None
    if args.journal:
        rec = load_journal(args.journal)
    elif args.demo:
        rec = demo_recorder(steps=args.steps)
    n_ev = traceview.write_trace(
        args.out, rec, step_seconds=args.step_seconds
    )
    print(f"wrote {args.out} ({n_ev} trace events) — open at "
          f"https://ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
