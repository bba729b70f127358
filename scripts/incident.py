#!/usr/bin/env python
"""Inspect flight-recorder incident bundles (`make incident-demo`).

Thin CLI over :mod:`mpi_grid_redistribute_tpu.telemetry.incident`. A
bundle directory is what the :class:`~...telemetry.incident
.FlightRecorder` froze when an ALERT or injected fault fired: the
retained journal window, all-time counts, the rendered OpenMetrics
exposition, health findings, flow snapshot, env fingerprint and the
triggering step context, indexed by ``index.json``
(layout: README "Incident response"). Three subcommands:

* ``list DIR`` — one line per bundle (id, rule, trigger, capture time,
  triggering trace id), oldest first; ``--json`` prints the raw index
  entries instead.
* ``show DIR ID`` — a bundle's full ``index.json`` plus which files are
  actually present on disk.
* ``export DIR ID --out TRACE.json`` — re-hydrate the bundle's frozen
  journal window into a Perfetto/Chrome trace (flow arrows link the
  causing step to the alert/restart/incident it produced — open at
  https://ui.perfetto.dev).

Examples:

  python scripts/incident.py list /tmp/incidents
  python scripts/incident.py show /tmp/incidents incident-0001-slo_latency_p99_s
  python scripts/incident.py export /tmp/incidents \\
      incident-0001-slo_latency_p99_s --out incident.trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def cmd_list(args) -> int:
    from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib

    entries = incident_lib.list_bundles(args.dir)
    if args.json:
        json.dump(entries, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0
    if not entries:
        print(f"no bundles under {args.dir}")
        return 0
    for e in entries:
        if "error" in e:
            print(f"{e.get('id', '?')}: UNREADABLE ({e['error']})")
            continue
        trace = (e.get("context") or {}).get("trace", "-")
        print(
            f"{e.get('id')}  rule={e.get('rule')}  "
            f"trigger={e.get('trigger')}  t={e.get('captured_at')}  "
            f"trace={trace}"
        )
    return 0


def cmd_show(args) -> int:
    from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib

    try:
        index = incident_lib.load_bundle(args.dir, args.id)
    except (OSError, ValueError) as exc:
        raise SystemExit(f"{args.dir}/{args.id}: {exc}")
    json.dump(index, sys.stdout, indent=2, sort_keys=True)
    print()
    return 0


def cmd_export(args) -> int:
    from mpi_grid_redistribute_tpu import telemetry
    from mpi_grid_redistribute_tpu.telemetry import traceview

    journal = os.path.join(args.dir, args.id, "journal.jsonl")
    if not os.path.isfile(journal):
        raise SystemExit(f"{journal}: no frozen journal in this bundle")
    # the frozen window is a normal to_jsonl export: re-hydrate it
    # through the aggregation layer (single shard) so the exported trace
    # is exactly what a pod merge of the same lines would show
    merged = telemetry.merge_journals([journal])
    rec = merged.to_recorder()
    n_ev = traceview.write_trace(args.out, rec)
    print(
        f"wrote {args.out} ({n_ev} trace events) — open at "
        f"https://ui.perfetto.dev"
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="List, inspect and export flight-recorder incident "
        "bundles (telemetry/incident.py)."
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_list = sub.add_parser("list", help="list bundles under a directory")
    p_list.add_argument("dir", help="incident bundle root")
    p_list.add_argument(
        "--json", action="store_true", help="print raw index entries"
    )
    p_list.set_defaults(fn=cmd_list)

    p_show = sub.add_parser("show", help="print one bundle's index")
    p_show.add_argument("dir", help="incident bundle root")
    p_show.add_argument("id", help="bundle id (see `list`)")
    p_show.set_defaults(fn=cmd_show)

    p_exp = sub.add_parser(
        "export", help="export a bundle's journal window to a Perfetto trace"
    )
    p_exp.add_argument("dir", help="incident bundle root")
    p_exp.add_argument("id", help="bundle id (see `list`)")
    p_exp.add_argument("--out", required=True, help="output trace JSON path")
    p_exp.set_defaults(fn=cmd_export)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
