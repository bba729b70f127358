"""Baseline (grandfathered-findings) support for gridlint.

A baseline file records findings that predate the linter and are
accepted as-is, so the check can gate *new* violations at zero while
old ones are paid down incrementally. Entries match on the
line-insensitive :meth:`Finding.baseline_key` — (rule, path, symbol,
message) — so unrelated edits that shift line numbers do not churn the
file. Every entry must carry a human-written ``justification``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from mpi_grid_redistribute_tpu.analysis.core import Finding

BaselineKey = Tuple[str, str, str, str]

_BASELINE_NAME = "gridlint_baseline.json"
_PROGPROFILE_NAME = "progprofile_baseline.json"
_SHARDCHECK_NAME = "shardcheck_baseline.json"
_RACECHECK_NAME = "racecheck_baseline.json"


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), _BASELINE_NAME)


def shardcheck_baseline_path() -> str:
    """The S001-S003 journal-suppression baseline (same schema and
    matching semantics as the gridlint baseline — :func:`load_baseline`
    / :func:`write_baseline` / :func:`split_baselined` apply verbatim;
    shardcheck findings use the program name as the symbol)."""
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), _SHARDCHECK_NAME
    )


def racecheck_baseline_path() -> str:
    """The T001-T005 suppression baseline (same schema and matching
    semantics as the gridlint baseline — :func:`load_baseline` /
    :func:`write_baseline` / :func:`split_baselined` apply verbatim).
    racecheck messages are built from line-insensitive thread-root
    labels, so entries survive unrelated edits."""
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), _RACECHECK_NAME
    )


def load_baseline(path: str) -> Set[BaselineKey]:
    """Read a baseline file into the set of suppressed finding keys.

    A missing file is an empty baseline. A malformed file is an error —
    silently ignoring it would un-gate every grandfathered finding.
    """
    if not os.path.exists(path):
        return set()
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    entries = data.get("findings", data if isinstance(data, list) else [])
    keys: Set[BaselineKey] = set()
    for e in entries:
        try:
            keys.add((e["rule"], e["path"], e["symbol"], e["message"]))
        except (TypeError, KeyError) as exc:
            raise SystemExit(
                f"gridlint: malformed baseline entry in {path}: {e!r} ({exc})"
            )
    return keys


_GRIDLINT_BASELINE_COMMENT = (
    "gridlint baseline: findings accepted at linter introduction. "
    "Matching is line-insensitive (rule, path, symbol, message). "
    "Remove entries as the underlying code is fixed; never add "
    "entries to dodge a new finding — fix or inline-suppress with "
    "a reason instead."
)


def write_baseline(
    path: str,
    findings: Sequence[Finding],
    justification: str = "grandfathered at baseline creation",
    comment: Optional[str] = None,
) -> None:
    entries = [
        {
            "rule": f.rule,
            "path": f.path,
            "symbol": f.symbol,
            "message": f.message,
            "justification": justification,
        }
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule))
    ]
    payload = {
        "comment": comment or _GRIDLINT_BASELINE_COMMENT,
        "findings": entries,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=False)
        fh.write("\n")


# ---------------------------------------------------------------------
# progcheck's J004 profile baseline (analysis/progprofile_baseline.json)
#
# Unlike the gridlint baseline (a suppression list), this one is a
# MEASUREMENT: the static wire/footprint profile of every registered
# program, compared exactly by ``rules_jaxpr.compare_profiles``. These
# helpers are jax-free, so the baseline can be read without importing
# the analyzer.
# ---------------------------------------------------------------------


def progprofile_baseline_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), _PROGPROFILE_NAME
    )


def load_progprofile_baseline(
    path: Optional[str] = None,
) -> Optional[Dict[str, dict]]:
    """name -> profile dict, or ``None`` when the file doesn't exist
    yet (progcheck then reports every program as unbaselined rather
    than crashing — same loud-but-recoverable posture as gridlint's
    malformed-baseline SystemExit)."""
    path = path or progprofile_baseline_path()
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    profiles = data.get("profiles")
    if not isinstance(profiles, dict):
        raise SystemExit(
            f"progcheck: malformed profile baseline {path}: expected a "
            "top-level 'profiles' object — regenerate with "
            "--update-baseline"
        )
    return profiles


_PROGPROFILE_COMMENT = (
    "progcheck J004 baseline: the static wire/footprint profile "
    "(collective bytes, peak live-buffer estimate) of every "
    "registered program, computed from jaxpr shapes x itemsize. "
    "Deterministic for a fixed program: any drift is a real "
    "cost-model change. Refresh with "
    "`python scripts/progcheck.py --update-baseline` and justify "
    "the delta in the commit message."
)


def _read_profile_doc(path: str) -> dict:
    """The full profile-baseline document, ``{}`` when absent. Both
    writers merge through this so progcheck's ``profiles`` section and
    shardcheck's ``wire_attribution`` section can refresh independently
    without clobbering each other."""
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        try:
            data = json.load(fh)
        except ValueError as exc:
            raise SystemExit(
                f"progcheck: malformed profile baseline {path}: {exc} — "
                "delete it and regenerate with --update-baseline"
            )
    if not isinstance(data, dict):
        raise SystemExit(
            f"progcheck: malformed profile baseline {path}: expected a "
            "top-level JSON object — regenerate with --update-baseline"
        )
    return data


def _write_profile_doc(path: str, doc: dict) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def write_progprofile_baseline(
    path: Optional[str], profiles: Dict[str, dict]
) -> None:
    path = path or progprofile_baseline_path()
    doc = _read_profile_doc(path)
    doc["comment"] = _PROGPROFILE_COMMENT
    doc["profiles"] = {k: profiles[k] for k in sorted(profiles)}
    _write_profile_doc(path, doc)


# -- shardcheck's S004 wire-attribution section ------------------------


def load_wire_baseline(
    path: Optional[str] = None,
) -> Optional[Dict[str, dict]]:
    """name -> wire-attribution dict from the ``wire_attribution``
    section, or ``None`` when the file or section doesn't exist yet
    (shardcheck then reports every program as unbaselined)."""
    path = path or progprofile_baseline_path()
    if not os.path.exists(path):
        return None
    doc = _read_profile_doc(path)
    section = doc.get("wire_attribution")
    if section is None:
        return None
    programs = section.get("programs") if isinstance(section, dict) else None
    if not isinstance(programs, dict):
        raise SystemExit(
            f"shardcheck: malformed wire_attribution section in {path}: "
            "expected {'comment': ..., 'programs': {...}} — regenerate "
            "with scripts/shardcheck.py --update-baseline"
        )
    return programs


def write_wire_baseline(path: Optional[str], wires: Dict[str, dict]) -> None:
    path = path or progprofile_baseline_path()
    doc = _read_profile_doc(path)
    doc.setdefault("comment", _PROGPROFILE_COMMENT)
    doc["wire_attribution"] = {
        "comment": (
            "shardcheck S004 baseline: per-mesh-axis and per-domain "
            "(ICI vs DCN, by axis-name convention) static wire "
            "attribution of every registered program. per_axis bills "
            "full operand bytes to every axis a collective crosses; "
            "per_domain bills each collective once to its most "
            "expensive domain, so it sums to J004's collective total. "
            "Refresh with `python scripts/shardcheck.py "
            "--update-baseline` and justify the delta in the commit "
            "message."
        ),
        "programs": {k: wires[k] for k in sorted(wires)},
    }
    _write_profile_doc(path, doc)


# -- kernelcheck's K003 VMEM-footprint table ---------------------------
#
# Its OWN file (kernelcheck_baseline.json): the footprint model is a
# deterministic function of the captured pallas_call anatomy, so the
# table is compared EXACTLY (rtol 0 by default) and any drift means the
# kernel's blocking actually changed. The ROADMAP item-3 megakernel
# must land a row here before it is ever compiled on a chip.

_KERNELCHECK_NAME = "kernelcheck_baseline.json"

_KERNELCHECK_COMMENT = (
    "kernelcheck K003 baseline: per-kernel VMEM live-footprint table "
    "from the captured pallas_call anatomy — (sublane, lane)-padded "
    "block buffers (x2 when the index map varies over the grid: the "
    "pipeline double-buffers) plus VMEM scratch, per site, with the "
    "peak across sites. Deterministic, compared exactly. Refresh with "
    "`python scripts/kernelcheck.py --update-baseline` and justify "
    "the footprint delta in the commit message."
)


def kernelcheck_baseline_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), _KERNELCHECK_NAME
    )


def load_kernelcheck_baseline(
    path: Optional[str] = None,
) -> Optional[Dict[str, dict]]:
    """name -> footprint dict from the ``footprints`` table, or
    ``None`` when the file doesn't exist yet (kernelcheck then reports
    every kernel as unbaselined)."""
    path = path or kernelcheck_baseline_path()
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        try:
            doc = json.load(fh)
        except json.JSONDecodeError as exc:
            raise SystemExit(
                f"kernelcheck: malformed baseline {path}: {exc} — "
                "regenerate with scripts/kernelcheck.py "
                "--update-baseline"
            )
    footprints = doc.get("footprints") if isinstance(doc, dict) else None
    if not isinstance(footprints, dict):
        raise SystemExit(
            f"kernelcheck: malformed baseline {path}: expected "
            "{'comment': ..., 'footprints': {...}} — regenerate with "
            "scripts/kernelcheck.py --update-baseline"
        )
    return footprints


def write_kernelcheck_baseline(
    path: Optional[str], footprints: Dict[str, dict]
) -> None:
    path = path or kernelcheck_baseline_path()
    doc = {
        "comment": _KERNELCHECK_COMMENT,
        "footprints": {k: footprints[k] for k in sorted(footprints)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def split_baselined(
    findings: Iterable[Finding], baseline: Set[BaselineKey]
) -> Tuple[List[Finding], List[Finding]]:
    """Partition into (new, grandfathered) against ``baseline``."""
    new: List[Finding] = []
    old: List[Finding] = []
    for f in findings:
        (old if f.baseline_key() in baseline else new).append(f)
    return new, old
