"""Semantic J-rules over traced jaxprs (progcheck's rule bodies).

Split from :mod:`.progcheck` the way gridlint splits rule bodies from
``analysis/core.py``: progcheck owns the walk API, registry and CLI;
this module owns what each rule MEANS. Everything here operates on
already-traced jaxprs — importing it never touches device state.

The one analysis with real machinery is J001's replication question.
The naive reading of "cond branches must issue identical collectives"
would condemn the repo's own count-driven engines: the sparse dispatch
cond deliberately carries ``all_to_all`` at B columns in one branch and
at the dense pool width in the other, and the neighbor cond has
ppermute on one side only. Those are SAFE because the predicate is the
one-scalar globally-agreed guard — ``ok`` reduced through ``lax.pmin``
— so every rank takes the SAME branch and the schedules never
interleave across ranks. J001 therefore fires only when branch
schedules mismatch AND the predicate is not provably replicated, where
"provably replicated" is answered by the shared per-mesh-axis vary-set
interpreter in :mod:`.shardcheck` (which grew out of the boolean
replication pass that used to live here): the predicate's inferred
vary-set must be empty. The collective vocabulary
(``COLLECTIVE_PRIMS``, :func:`collective_axes`,
:func:`collective_signature`) lives in shardcheck too and is
re-exported here for compatibility.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from mpi_grid_redistribute_tpu.analysis.progcheck import (
    ProgFinding,
    ProgramSpec,
    aval_bytes,
    branch_jaxprs,
    dispatch_conds,
    has_primitive,
    jaxpr_of,
    subjaxprs,
    walk_eqns,
)
from mpi_grid_redistribute_tpu.analysis.shardcheck import (
    COLLECTIVE_PRIMS,
    collective_axes,
    collective_signature,
)
from mpi_grid_redistribute_tpu.analysis.shardcheck import (  # noqa: F401
    CALL_PRIMS as _CALL_PRIMS,
    REPLICATING_PRIMS as _REPLICATING_PRIMS,
    VARYING_PRIMS as _VARYING_PRIMS,
    _sig_entry,
)

RULE_DOCS = {
    "J000": "registry completeness: every engine x topology, the resident "
    "macro-step, the migrate fast path and apply_assignment must have a "
    "registered program",
    "J001": "collective-schedule consistency: cond/switch branches with "
    "collectives must have identical ordered collective signatures, or a "
    "provably replicated (pmin-agreed one-scalar) predicate",
    "J002": "resident purity: no callback/infeed/outfeed/debug primitives "
    "anywhere in a resident-marked program",
    "J003": "fast-path cost contract: dispatch cond present; migrate fast "
    "branches sort-free with mover-bounded gathers; sparse wire at "
    "mover-cap columns; neighbor wire ppermute-only, no dense all_to_all; "
    "pipelined steady-state body bins step k+1 before landing step k, with "
    "exactly one landing scatter (free-stack update fused, no "
    "dynamic_update_slice) and at most one payload collective per "
    "iteration",
    "J004": "static wire/footprint drift: per-program collective bytes and "
    "peak live-buffer estimates must match the committed "
    "progprofile_baseline.json",
}

_HOST_SYNC_MARKERS = ("callback", "infeed", "outfeed", "debug")


# ---------------------------------------------------------------------
# J001 — collective-schedule consistency across cond branches
# ---------------------------------------------------------------------


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")  # core.Literal; Vars have no .val


def check_j001(closed, spec: ProgramSpec) -> List[ProgFinding]:
    """One :func:`shardcheck.analyze` pass records every cond site with
    its predicate vary-set and per-branch collective signatures; J001
    fires where the schedules mismatch and the vary-set is non-empty
    (the predicate is not provably identical on every rank)."""
    from mpi_grid_redistribute_tpu.analysis import shardcheck

    report = shardcheck.analyze(closed)
    findings: Set[ProgFinding] = set()
    for site in report.conds:
        sigs = site.signatures
        if any(sigs) and len(set(sigs)) > 1 and site.pred_vary:
            detail = "; ".join(
                f"branch{i}=[{', '.join(s) if s else ''}]"
                for i, s in enumerate(sigs)
            )
            findings.add(
                ProgFinding(
                    "J001",
                    spec.name,
                    "cond branches issue mismatched collective schedules "
                    "and the predicate is not provably replicated (no "
                    "pmin/psum-agreed one-scalar guard): ranks can "
                    f"diverge and deadlock the mesh — {detail}",
                )
            )
    return sorted(findings, key=lambda f: f.message)


# ---------------------------------------------------------------------
# J002 — resident purity
# ---------------------------------------------------------------------


def check_j002(closed, spec: ProgramSpec) -> List[ProgFinding]:
    if not spec.resident:
        return []
    hostile = sorted(
        {
            e.primitive.name
            for e in walk_eqns(closed)
            if any(m in e.primitive.name for m in _HOST_SYNC_MARKERS)
        }
    )
    if not hostile:
        return []
    return [
        ProgFinding(
            "J002",
            spec.name,
            "resident-marked program traces host-sync primitives "
            f"{hostile}: every occurrence splits the chunk and stalls "
            "the macro-step (dynamic backstop behind gridlint G009)",
        )
    ]


# ---------------------------------------------------------------------
# J003 — fast-path cost contract
# ---------------------------------------------------------------------


def _gather_out_rows(eqn) -> int:
    return max(
        int(np.prod(v.aval.shape[1:])) if v.aval.shape else 1
        for v in eqn.outvars
    )


def _check_migrate(closed, spec) -> List[ProgFinding]:
    conds = dispatch_conds(closed, lambda b: has_primitive(b, "sort"))
    if not conds:
        return [
            ProgFinding(
                "J003",
                spec.name,
                "migrate fast path lost: no cond whose branches disagree "
                "about sorting (dense sorts residents, the fast branch "
                "must not sort at all)",
            )
        ]
    out: List[ProgFinding] = []
    bound = spec.resident_rows
    for _eqn, fast, _dense in conds:
        if has_primitive(fast, "all_to_all"):
            out.append(
                ProgFinding(
                    "J003",
                    spec.name,
                    "migrate fast branch contains a dense all_to_all — "
                    "the mover-scale wire contract is gone",
                )
            )
        for e in walk_eqns(fast):
            if e.primitive.name == "gather" and bound is not None:
                rows = _gather_out_rows(e)
                if rows >= bound:
                    out.append(
                        ProgFinding(
                            "J003",
                            spec.name,
                            f"fast-branch gather produces {rows} rows >= "
                            f"resident count {bound}: a resident-scale "
                            "permutation snuck into the mover-scale path",
                        )
                    )
    return out


def _check_sparse_wire(closed, spec) -> List[ProgFinding]:
    # both branches exchange (sparse rides all_to_all at B, not cap,
    # columns per destination), so find the dispatch cond by branch
    # all_to_all operand widths
    widths = []
    for eqn in walk_eqns(closed):
        if eqn.primitive.name != "cond":
            continue
        per_branch = []
        for b in branch_jaxprs(eqn):
            w = [
                int(np.prod(e.invars[0].aval.shape))
                for e in walk_eqns(b)
                if e.primitive.name == "all_to_all"
            ]
            per_branch.append(max(w) if w else 0)
        if len(set(per_branch)) == 2 and min(per_branch) > 0:
            widths.append(sorted(per_branch))
    if not widths:
        return [
            ProgFinding(
                "J003",
                spec.name,
                "sparse dispatch cond lost: no cond separates a narrow "
                "(mover-cap) all_to_all pool from the dense pool",
            )
        ]
    out: List[ProgFinding] = []
    cap, B = spec.capacity, spec.mover_cap
    for narrow, wide in widths:
        if cap and B and narrow * cap != wide * B:
            out.append(
                ProgFinding(
                    "J003",
                    spec.name,
                    f"sparse pool width broke the B/cap contract: narrow "
                    f"{narrow} * cap {cap} != wide {wide} * mover_cap {B} "
                    "— the fast branch no longer rides mover-cap columns",
                )
            )
    return out


def _check_neighbor_wire(closed, spec) -> List[ProgFinding]:
    conds = dispatch_conds(
        closed, lambda b: has_primitive(b, "all_to_all")
    )
    if not conds:
        return [
            ProgFinding(
                "J003",
                spec.name,
                "neighbor dispatch cond lost: no cond whose branches "
                "disagree about all_to_all (fast ppermute schedule vs "
                "dense pool exchange)",
            )
        ]
    out: List[ProgFinding] = []
    for _eqn, fast, dense in conds:
        if not has_primitive(fast, "ppermute"):
            out.append(
                ProgFinding(
                    "J003",
                    spec.name,
                    "neighbor fast branch has no ppermute: the stencil "
                    "shift schedule is gone",
                )
            )
        if has_primitive(dense, "ppermute"):
            out.append(
                ProgFinding(
                    "J003",
                    spec.name,
                    "neighbor dense branch contains ppermute: the "
                    "fallback is no longer the canonical dense exchange",
                )
            )
    return out


# Collectives that move particle payload (vs scalar-reduction guards):
# the pipelined contract allows at most ONE of these per steady-state
# iteration — a second one means the two-phase split re-acquired a
# separate completion exchange.
_PAYLOAD_COLLECTIVES = frozenset(
    {"ppermute", "pshuffle", "all_to_all", "all_gather",
     "all_gather_invariant", "psum_scatter", "reduce_scatter"}
)


def floor_before_scatter(jaxpr) -> bool:
    """Does this (sub)jaxpr bin (``floor`` — the cell quantization in
    ``binning.cell_of_position_planar``) before its first landing
    ``scatter``, in depth-first trace order? The pipelined steady-state
    branch does (step k+1's binning is issued against pre-landing rows);
    the sequential branch lands first and bins after. Shared by the
    J003 pipeline checker and the test suite's jaxpr-ordering assert."""
    for e in walk_eqns(jaxpr):
        if e.primitive.name == "floor":
            return True
        if e.primitive.name == "scatter":
            return False
    return False


def _check_pipeline(closed, spec) -> List[ProgFinding]:
    conds = dispatch_conds(closed, floor_before_scatter)
    if not conds:
        return [
            ProgFinding(
                "J003",
                spec.name,
                "pipelined dispatch cond lost: no cond separates an "
                "overlapped branch (step k+1 binning issued before step "
                "k's landing scatter) from the sequential land-then-bin "
                "body",
            )
        ]
    out: List[ProgFinding] = []
    for _eqn, seq, pipe in conds:
        for label, b in (("sequential", seq), ("pipelined", pipe)):
            n_scatter = sum(
                1 for e in walk_eqns(b) if e.primitive.name == "scatter"
            )
            if n_scatter != 1:
                out.append(
                    ProgFinding(
                        "J003",
                        spec.name,
                        f"{label} branch lands with {n_scatter} scatters "
                        "(contract: exactly one — the free-stack update "
                        "must stay fused into the landing scatter)",
                    )
                )
            if has_primitive(b, "dynamic_update_slice"):
                out.append(
                    ProgFinding(
                        "J003",
                        spec.name,
                        f"{label} branch contains dynamic_update_slice: "
                        "the free-stack update split back out of the "
                        "fused landing",
                    )
                )
            n_coll = sum(
                1
                for e in walk_eqns(b)
                if e.primitive.name in _PAYLOAD_COLLECTIVES
            )
            if n_coll > 1:
                out.append(
                    ProgFinding(
                        "J003",
                        spec.name,
                        f"{label} branch issues {n_coll} payload "
                        "collectives per steady-state iteration "
                        "(contract: at most one exchange per step)",
                    )
                )
    return out


_FASTPATH_CHECKS = {
    "migrate": _check_migrate,
    "sparse_wire": _check_sparse_wire,
    "neighbor_wire": _check_neighbor_wire,
    "pipeline": _check_pipeline,
}


def check_j003(closed, spec: ProgramSpec) -> List[ProgFinding]:
    if spec.fastpath is None:
        return []
    try:
        checker = _FASTPATH_CHECKS[spec.fastpath]
    except KeyError:
        raise ValueError(
            f"program {spec.name!r}: unknown fastpath kind "
            f"{spec.fastpath!r} (known: {sorted(_FASTPATH_CHECKS)})"
        ) from None
    return checker(closed, spec)


# ---------------------------------------------------------------------
# J004 — static wire/footprint model + drift gate
# ---------------------------------------------------------------------


def _merge(total: Dict[str, int], add: Dict[str, int], mult: int = 1):
    for k, v in add.items():
        total[k] = total.get(k, 0) + v * mult


def _collective_cost(jaxpr) -> Tuple[Dict[str, int], int]:
    """(bytes per collective primitive, collective eqn count) for one
    jaxpr: scan bodies multiplied by trip count, cond billed at the
    max-bytes branch (the wire you pay when the fast path misses),
    while bodies billed at one trip (trip count is dynamic; the model
    only needs determinism, not exactness)."""
    total: Dict[str, int] = {}
    count = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            best: Tuple[Dict[str, int], int] = ({}, 0)
            best_bytes = -1
            for b in branch_jaxprs(eqn):
                d, c = _collective_cost(b)
                s = sum(d.values())
                if s > best_bytes:
                    best_bytes, best = s, (d, c)
            _merge(total, best[0])
            count += best[1]
        elif name == "scan":
            mult = int(eqn.params.get("length", 1))
            for sub in subjaxprs(eqn):
                d, c = _collective_cost(jaxpr_of(sub))
                _merge(total, d, mult)
                count += c * mult
        elif name in COLLECTIVE_PRIMS:
            b = sum(aval_bytes(v.aval) for v in eqn.invars)
            total[name] = total.get(name, 0) + b
            count += 1
        else:
            for sub in subjaxprs(eqn):
                d, c = _collective_cost(jaxpr_of(sub))
                _merge(total, d)
                count += c
    return total, count


def _peak_live_bytes(jaxpr) -> int:
    """Peak simultaneously-live buffer bytes of ONE jaxpr body under a
    linear-scan liveness model (vars die at their last textual use).
    Not XLA's allocator — a deterministic monotone proxy: widening any
    buffer can only raise it, which is what a drift gate needs."""
    eqns = jaxpr.eqns
    last_use: Dict[object, int] = {}
    for i, eqn in enumerate(eqns):
        for a in eqn.invars:
            if not _is_literal(a):
                last_use[a] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = len(eqns)
    live = 0
    sizes: Dict[object, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        sizes[v] = aval_bytes(v.aval)
        live += sizes[v]
    peak = live
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            sizes[v] = aval_bytes(v.aval)
            live += sizes[v]
        peak = max(peak, live)
        for v in list(eqn.invars) + list(eqn.outvars):
            if not _is_literal(v) and last_use.get(v, i) <= i and v in sizes:
                live -= sizes.pop(v)
    return peak


def _all_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in subjaxprs(eqn):
            yield from _all_jaxprs(jaxpr_of(sub))


def program_profile(closed) -> dict:
    """The static cost profile J004 gates: collective byte totals and the
    peak-live estimate, all from jaxpr shapes x itemsize — deterministic
    for a fixed program, so the baseline compare is exact."""
    j = jaxpr_of(closed)
    coll, count = _collective_cost(j)
    peak = max(_peak_live_bytes(sub) for sub in _all_jaxprs(j))
    return {
        "collective_bytes": {k: int(v) for k, v in sorted(coll.items())},
        "collective_bytes_total": int(sum(coll.values())),
        "collective_count": int(count),
        "peak_live_bytes": int(peak),
        "eqn_count": sum(1 for _ in walk_eqns(j)),
    }


_PROFILE_SCALARS = (
    "collective_bytes_total",
    "collective_count",
    "peak_live_bytes",
    "eqn_count",
)


def _drifted(old: int, new: int, rtol: float) -> bool:
    if old == new:
        return False
    if rtol <= 0:
        return True
    return abs(new - old) > rtol * max(abs(old), 1)


def compare_profiles(
    current: Dict[str, dict],
    baseline: Optional[Dict[str, dict]],
    rtol: float = 0.0,
    check_stale: bool = False,
    partial: bool = False,
) -> List[ProgFinding]:
    """Drift gate over the static profiles. Any numeric drift beyond
    ``rtol`` (default: exact) is a J004 finding — intentional changes
    re-commit via ``--update-baseline``, exactly like the gridlint
    baseline workflow."""
    findings: List[ProgFinding] = []
    if baseline is None:
        baseline = {}
    for name in sorted(current):
        if name not in baseline:
            findings.append(
                ProgFinding(
                    "J004",
                    name,
                    "program has no committed profile baseline — run "
                    "scripts/progcheck.py --update-baseline and commit "
                    "analysis/progprofile_baseline.json",
                )
            )
            continue
        cur, base = current[name], baseline[name]
        for key in _PROFILE_SCALARS:
            old, new = int(base.get(key, 0)), int(cur.get(key, 0))
            if _drifted(old, new, rtol):
                pct = (new - old) / max(abs(old), 1) * 100.0
                findings.append(
                    ProgFinding(
                        "J004",
                        name,
                        f"{key} drifted: baseline {old}, now {new} "
                        f"({pct:+.1f}%) — a static cost change; justify "
                        "it and refresh with --update-baseline",
                    )
                )
        old_c = dict(base.get("collective_bytes", {}))
        new_c = dict(cur.get("collective_bytes", {}))
        for prim in sorted(set(old_c) | set(new_c)):
            old, new = int(old_c.get(prim, 0)), int(new_c.get(prim, 0))
            if _drifted(old, new, rtol):
                findings.append(
                    ProgFinding(
                        "J004",
                        name,
                        f"collective {prim} bytes drifted: baseline "
                        f"{old}, now {new} — the wire schedule changed; "
                        "justify it and refresh with --update-baseline",
                    )
                )
    if check_stale and not partial:
        for name in sorted(set(baseline) - set(current)):
            findings.append(
                ProgFinding(
                    "J004",
                    name,
                    "stale baseline entry: program is no longer "
                    "registered — remove it with --update-baseline",
                )
            )
    return findings
