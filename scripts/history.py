#!/usr/bin/env python
"""Run index: every journal-store run under a directory, one view.

A service driver started with ``--store-dir`` leaves a
``telemetry.store`` journal-store root behind. This script indexes the
roots under a directory into one run-index: writer, span, exact event
totals, the merged-store p99 and the segment footprint of each run.

Modes:

  # human view: the indexed store runs, newest first
  python scripts/history.py --stores DIR

  # machine view: the full index as JSON (tooling / grid_top feeds)
  python scripts/history.py --stores DIR --json

Without ``--stores`` no directory is scanned and the index is empty.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def index_stores(root):
    """Index journal-store runs under ``root``: writer, span, exact
    event totals and the merged-store p99 per run, newest first."""
    from mpi_grid_redistribute_tpu.telemetry import store as store_lib

    entries = []
    for store_root in store_lib.list_stores(root):
        try:
            reader = store_lib.StoreReader(store_root)
        except store_lib.StoreCorruptError as e:
            entries.append({"root": store_root, "error": str(e)})
            continue
        man = reader.manifest
        counts = reader.counts()
        h = reader.latency_histogram()
        entries.append(
            {
                "root": store_root,
                "writer": man.get("writer"),
                "created": man.get("created"),
                "updated": man.get("updated"),
                "events_total": sum(counts.values()),
                "steps": counts.get("step_latency", 0),
                "p99_s": h.quantile(0.99) if h.count else None,
                "segments": len(man.get("segments", [])),
                "retired": man.get("retired", {}).get("segments", 0),
                "bytes": sum(s["bytes"] for s in man.get("segments", []))
                + (man.get("active") or {}).get("bytes", 0),
            }
        )
    return entries


def render_runs(stores):
    """Human view: each indexed store run."""
    lines = ["run history"]
    if not stores:
        lines.append("  (no store runs indexed; pass --stores DIR)")
    else:
        lines.append("  store runs (newest first)")
    for s in stores:
        if s.get("error"):
            lines.append(f"    corrupt: {s['root']}: {s['error']}")
            continue
        writer = s.get("writer") or {}
        p99 = s.get("p99_s")
        lines.append(
            f"    {s['root']}  steps={s['steps']}"
            f"  events={s['events_total']}"
            + (f"  p99={p99:.4g}s" if p99 is not None else "")
            + f"  segs={s['segments']}(+{s['retired']})"
            + (
                f"  writer={writer.get('host')}:{writer.get('pid')}"
                if writer
                else ""
            )
        )
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Index journal-store runs and render them."
    )
    p.add_argument(
        "--stores",
        metavar="DIR",
        help="directory to scan for journal-store roots (each child "
        "with a MANIFEST.json is one run)",
    )
    p.add_argument("--json", action="store_true",
                   help="print the run-index as JSON and exit")
    args = p.parse_args(argv)

    index = {"stores": index_stores(args.stores) if args.stores else []}
    if args.json:
        json.dump(index, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0

    sys.stdout.write(render_runs(index["stores"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
