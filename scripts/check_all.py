#!/usr/bin/env python
"""Umbrella CI gate: every analyzer family, one SARIF file.

Usage:
    python scripts/check_all.py [--sarif-out PATH] [--analyzers A,B]
    python scripts/check_all.py --lint

The ANALYZERS registry below is the single source of truth for the
family list — the umbrella test, ``make check`` and ``make lint`` all
derive from it, so adding a family means adding one row here (not
hand-bumping an N-tool count in the tests). Each analyzer runs in its
own subprocess so the pure-AST tools stay jax-free and the jaxpr
analyzers get their wrapper-forced environments (virtual CPU mesh,
pinned CPU platform). In the default (SARIF) mode the runs are merged
into one document via ``analysis/sarif.py``'s ``merge_sarif`` — a
single code-scanning upload for ``make check``; ``--lint`` runs the
same registry in plain-text ``--check`` mode for the developer loop.
Per-analyzer wall-time is printed either way so lint growth stays
visible.

Exit codes: 0 when every tool is clean, 1 when any tool found
something, 2 on any usage/parse error.
"""

import argparse
import collections
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Analyzer = collections.namedtuple("Analyzer", ["name", "cmd", "baseline"])

# name -> (runner argv, committed baseline the --check gate compares
# against). ``--format=sarif`` is appended at run time so --lint can
# reuse the same rows in text mode.
ANALYZERS = (
    Analyzer(
        "gridlint",
        ["scripts/gridlint.py", "mpi_grid_redistribute_tpu/", "--check"],
        "mpi_grid_redistribute_tpu/analysis/gridlint_baseline.json",
    ),
    Analyzer(
        "progcheck",
        ["scripts/progcheck.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/progprofile_baseline.json",
    ),
    Analyzer(
        "shardcheck",
        ["scripts/shardcheck.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/progprofile_baseline.json",
    ),
    Analyzer(
        "racecheck",
        ["scripts/racecheck.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/racecheck_baseline.json",
    ),
    Analyzer(
        "kernelcheck",
        ["scripts/kernelcheck.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/kernelcheck_baseline.json",
    ),
    Analyzer(
        "incident-demo",
        ["scripts/incident_demo.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/incident_demo_baseline.json",
    ),
    Analyzer(
        "storecheck",
        ["scripts/storecheck.py", "--check"],
        "mpi_grid_redistribute_tpu/analysis/storecheck_baseline.json",
    ),
)


def _select(spec):
    if not spec:
        return list(ANALYZERS)
    by_name = {a.name: a for a in ANALYZERS}
    wanted = [s.strip() for s in spec.split(",") if s.strip()]
    unknown = [w for w in wanted if w not in by_name]
    if unknown:
        print(
            f"check: unknown analyzer(s): {', '.join(unknown)} "
            f"(known: {', '.join(by_name)})",
            file=sys.stderr,
        )
        return None
    return [by_name[w] for w in wanted]


def main(argv=None) -> int:
    sys.path.insert(0, REPO)
    from mpi_grid_redistribute_tpu.analysis.sarif import merge_sarif

    p = argparse.ArgumentParser(
        prog="check_all",
        description="Run every registered analyzer and merge their "
        "SARIF runs into one file.",
    )
    p.add_argument(
        "--sarif-out",
        default=os.path.join(REPO, "analysis_merged.sarif"),
        metavar="PATH",
        help="merged SARIF output path (default: analysis_merged.sarif "
        "at the repo root)",
    )
    p.add_argument(
        "--analyzers",
        default=None,
        metavar="NAME[,NAME]",
        help="comma-separated subset of the registry to run (fast "
        "local loops); default: all "
        f"({', '.join(a.name for a in ANALYZERS)})",
    )
    p.add_argument(
        "--lint",
        action="store_true",
        help="plain-text mode: run each analyzer's --check without "
        "SARIF capture or merging (the `make lint` surface)",
    )
    args = p.parse_args(argv)

    selected = _select(args.analyzers)
    if selected is None:
        return 2

    docs = []
    worst = 0
    for tool in selected:
        cmd = tool.cmd + ([] if args.lint else ["--format=sarif"])
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable] + cmd,
            cwd=REPO,
            capture_output=True,
            text=True,
        )
        dt = time.monotonic() - t0
        if proc.returncode == 2:
            print(f"check: {tool.name} usage/parse error:", file=sys.stderr)
            sys.stderr.write(proc.stderr)
            return 2
        if args.lint:
            status = "clean" if proc.returncode == 0 else "FAILED"
            print(
                f"check: {tool.name} {status} "
                f"(exit {proc.returncode}, {dt:.1f}s)"
            )
            if proc.returncode != 0 and proc.stdout.strip():
                sys.stdout.write(proc.stdout)
            if proc.stderr.strip():
                sys.stderr.write(proc.stderr)
            worst = max(worst, proc.returncode)
            continue
        try:
            doc = json.loads(proc.stdout)
        except ValueError:
            print(
                f"check: {tool.name} produced no parseable SARIF "
                f"(exit {proc.returncode}):",
                file=sys.stderr,
            )
            sys.stderr.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            return 2
        docs.append(doc)
        n_results = sum(len(r.get("results", [])) for r in doc.get("runs", []))
        status = "clean" if proc.returncode == 0 else "FAILED"
        print(
            f"check: {tool.name} {status} "
            f"({n_results} finding(s), exit {proc.returncode}, {dt:.1f}s)"
        )
        # stale-baseline notes ride stderr; keep them visible
        if proc.stderr.strip():
            sys.stderr.write(proc.stderr)
        worst = max(worst, proc.returncode)

    if args.lint:
        return 1 if worst else 0

    merged = merge_sarif(docs)
    with open(args.sarif_out, "w", encoding="utf-8") as fh:
        json.dump(merged, fh, indent=2)
        fh.write("\n")
    print(
        f"check: merged {len(merged['runs'])} run(s) -> {args.sarif_out}"
    )
    return 1 if worst else 0


if __name__ == "__main__":
    sys.exit(main())
