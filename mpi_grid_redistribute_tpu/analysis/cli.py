"""gridlint command-line interface.

Exit codes: 0 — clean (or everything baselined); 1 — non-baselined
violations; 2 — usage error or unparseable input. ``--check`` is the CI
entry point (same semantics, but also fails on a stale baseline entry
that no longer matches anything, so the baseline can only shrink).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Sequence

from mpi_grid_redistribute_tpu.analysis.baseline import (
    default_baseline_path,
    load_baseline,
    split_baselined,
    write_baseline,
)
from mpi_grid_redistribute_tpu.analysis.core import (
    RULE_IDS,
    run_gridlint,
)

_RULE_DOCS = {
    "G001": "no data-dependent collectives in shard_map bodies; "
    "axis_name literals must be declared mesh axes",
    "G002": "no host syncs (.item/device_get/np.asarray/int()/float()) "
    "in jit-reachable code",
    "G003": "no dynamic-shape escapes (unsized nonzero/unique/where, "
    "boolean-mask indexing) in jitted code",
    "G004": "fuse_fields/bitcast call paths must carry a dtype.itemsize "
    "guard (planar 32-bit row contract)",
    "G005": "pallas_call must pass explicit grid and BlockSpecs; "
    "program_id-derived indices must be bounded",
    "G006": "no sorts or arange-indexed full-array takes inside "
    "fastpath-engine-marked functions (mover-sparse cost contract)",
    "G007": "no jax imports or device syncs in scrape-path-marked "
    "modules (the metrics plane is host-only)",
    "G008": "no bare `except:` or swallowed exceptions in "
    "service-path-marked modules (the supervisor must see every fault)",
    "G009": "no host syncs (np.asarray/.block_until_ready()/float() on "
    "non-literals) inside resident-path-marked functions (chunk "
    "interior stays on device)",
    "G010": "fastpath-engine/resident-path-marked functions must "
    "contain at least one named_scope/traced_span (layer attribution "
    "of profiler traces)",
}


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gridlint",
        description="AST-based SPMD/JIT invariant checker for "
        "mpi_grid_redistribute_tpu.",
    )
    p.add_argument(
        "paths",
        nargs="*",
        default=["mpi_grid_redistribute_tpu/"],
        help="files or directories to scan (default: the package)",
    )
    p.add_argument(
        "--format",
        choices=("text", "json", "sarif", "github"),
        default="text",
        help="output format (sarif: SARIF 2.1.0 for code-scanning "
        "upload; github: ::warning workflow-command annotation lines)",
    )
    p.add_argument(
        "--rules",
        default=None,
        metavar="G00x[,G00y]",
        help="comma-separated subset of rules to run",
    )
    p.add_argument(
        "--baseline",
        default=None,
        metavar="PATH",
        help=f"baseline file (default: {default_baseline_path()})",
    )
    p.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore the baseline; report every finding",
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current findings to the baseline file and exit 0",
    )
    p.add_argument(
        "--check",
        action="store_true",
        help="CI mode: additionally fail on stale baseline entries",
    )
    p.add_argument(
        "--check-baseline",
        action="store_true",
        help="baseline hygiene only: report stale baseline entries (no "
        "longer matching any finding) without gating new findings",
    )
    p.add_argument(
        "--root",
        default=None,
        help="path-relativization root (default: cwd)",
    )
    p.add_argument(
        "--list-rules", action="store_true", help="list rules and exit"
    )
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _parser().parse_args(argv)

    if args.list_rules:
        for rid in RULE_IDS:
            print(f"{rid}  {_RULE_DOCS[rid]}")
        return 0

    rules: Optional[List[str]] = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rules if r not in RULE_IDS]
        if unknown:
            print(
                f"gridlint: unknown rule(s): {', '.join(unknown)} "
                f"(known: {', '.join(RULE_IDS)})",
                file=sys.stderr,
            )
            return 2

    try:
        findings = run_gridlint(args.paths, root=args.root, rules=rules)
    except SystemExit as e:  # parse errors from build_project
        print(str(e), file=sys.stderr)
        return 2

    baseline_path = args.baseline or default_baseline_path()
    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(
            f"gridlint: wrote {len(findings)} finding(s) to {baseline_path}"
        )
        return 0

    baseline = set() if args.no_baseline else load_baseline(baseline_path)
    new, grandfathered = split_baselined(findings, baseline)

    stale: List[tuple] = []
    if (args.check or args.check_baseline) and baseline:
        matched = {f.baseline_key() for f in grandfathered}
        stale = sorted(baseline - matched)

    if args.check_baseline:
        # hygiene-only mode: stale suppressions rot silently unless
        # something gates them on their own — new findings are gridlint
        # --check's job, not this one's
        for key in stale:
            print(
                f"stale baseline entry (code fixed? remove it): "
                f"{key[0]} {key[1]} [{key[2]}]"
            )
        print(
            f"gridlint: {len(stale)} stale baseline entr(y/ies) of "
            f"{len(baseline)}"
        )
        return 1 if stale else 0

    if args.format == "json":
        print(
            json.dumps(
                {
                    "findings": [f.to_dict() for f in new],
                    "baselined": len(grandfathered),
                    "stale_baseline": [list(k) for k in stale],
                },
                indent=2,
            )
        )
    elif args.format in ("sarif", "github"):
        from mpi_grid_redistribute_tpu.analysis import sarif as sarif_lib

        if args.format == "sarif":
            print(
                json.dumps(
                    sarif_lib.to_sarif(new, "gridlint", _RULE_DOCS),
                    indent=2,
                )
            )
        else:
            for line in sarif_lib.github_annotations(new):
                print(line)
        # stale entries have no source location to annotate; keep them
        # visible (and exit-code-relevant) on stderr
        for key in stale:
            print(
                f"stale baseline entry (code fixed? remove it): "
                f"{key[0]} {key[1]} [{key[2]}]",
                file=sys.stderr,
            )
    else:
        for f in new:
            print(f.render())
        for key in stale:
            print(
                f"stale baseline entry (code fixed? remove it): "
                f"{key[0]} {key[1]} [{key[2]}]"
            )
        summary = f"gridlint: {len(new)} finding(s)"
        if grandfathered:
            summary += f", {len(grandfathered)} baselined"
        if stale:
            summary += f", {len(stale)} stale baseline entr(y/ies)"
        print(summary)

    return 1 if (new or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
