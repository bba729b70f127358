"""shardcheck — sharding/replication abstract interpreter over jaxprs.

progcheck's J001 used to carry a private boolean replication pass that
answered exactly one question at exactly one kind of program point: "is
this cond predicate identical on every rank?". ROADMAP item 2 (the
hierarchical ICI/DCN mesh) needs the general form of that question
answered for EVERY intermediate value: which mesh axes does each var
vary over? This module is that pass, promoted to a standalone forward
abstract interpreter, plus the S-rule family built on top of it
(:mod:`.rules_shard`).

Lattice
-------
Each var is mapped to a *vary-set*: the ``frozenset`` of mesh axis
names the value may differ over between ranks. ``frozenset()`` means
provably replicated on every axis; join is set union, so the analysis
is monotone and scan/while carries reach a fixpoint. Transfer rules:

* top-level invars, literals and closed-over constants: replicated;
* ``shard_map`` body invars: the axes their in_spec partitions (an
  empty spec dict — ``P()`` — is a fully replicated broadcast), plus
  any taint the outer operand already carried;
* ``psum``/``pmin``/``pmax``/``pmean`` (no ``axis_index_groups``),
  ``all_gather``, ``pbroadcast``: remove the reduced axes;
* ``all_to_all``/``psum_scatter``/``reduce_scatter``/``pshuffle``:
  add the communicated axes; ``axis_index``: exactly its axes;
* ``ppermute`` with a FULL permutation of the axis (every source and
  destination covered once) is lattice-identity — a replicated operand
  stays replicated under any rotation, including the identity; a
  partial perm zero-fills uncovered ranks and adds its axes;
* ``cond``: branch-output join plus the predicate's vary-set;
  ``scan``/``while``: union fixpoint over the carry (while also joins
  the cond-jaxpr predicate — a rank-varying trip count makes every
  carry rank-varying); ``jit``/call-like prims map through the body;
  unknown prims with sub-jaxprs conservatively poison their outputs to
  every in-scope axis;
* everything elementwise/default: union of the inputs.

The interpreter also records the program points the S-rules judge:
every ``cond`` site (predicate vary-set + per-branch collective
signatures — J001 consumes these), every full reduction whose operand
was already replicated on a reduced axis (S002), and every escape of a
varying value to a host-visible surface (S001/S003).

Rules (bodies in :mod:`.rules_shard`)
-------------------------------------
========  ==============================================================
S001      output-replication consistency: a shard_map output declared
          fully replicated (out_specs ``P()``) must be PROVABLY
          replicated on all mesh axes — stats scalars, dispatch
          predicates and grow counters the host reads must not be
          rank-dependent.
S002      redundant collective: a full ``psum``/``pmin``/``pmax``/
          ``pmean`` whose operand is already replicated on a reduced
          axis pays wire for a value every rank holds (``psum`` of a
          replicated x is a local ``x * axis_size``). A wire-cost
          optimization flag, journal-suppressed via
          ``analysis/shardcheck_baseline.json``.
S003      varying-value escape: a value still varying on some mesh
          axis reaches a scan ``ys`` leaf or a program output the host
          reads unreduced — the semantic complement of G002/J002.
S004      per-axis static wire attribution: J004's byte model split by
          the mesh axis each collective crosses, rolled up into an
          ICI-vs-DCN table and drift-gated against the
          ``wire_attribution`` section of
          ``analysis/progprofile_baseline.json``.
========  ==============================================================

CLI: ``python scripts/shardcheck.py [--format=json|sarif|github]
[--check] [--update-baseline]`` — exit codes mirror gridlint (0 clean,
1 findings/drift, 2 usage error). ``make shardcheck`` wires it into
``make lint``; ``make check`` merges all three analyzers into one
SARIF file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from mpi_grid_redistribute_tpu.analysis.progcheck import (
    ProgramSpec,
    branch_jaxprs,
    default_programs,
    jaxpr_of,
    subjaxprs,
    trace_program,
    walk_eqns,
)

S_RULE_IDS = ("S001", "S002", "S003", "S004")

# ---------------------------------------------------------------------
# collective vocabulary (shared with rules_jaxpr, which re-exports it)
# ---------------------------------------------------------------------

# Cross-device communication primitives (jaxpr names; jax 0.9 writes
# ``psum``/``pmean`` inside shard_map as ``psum_invariant``).
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "psum_invariant",
        "pmax",
        "pmin",
        "pmean",
        "ppermute",
        "pshuffle",
        "all_to_all",
        "all_gather",
        "all_gather_invariant",
        "psum_scatter",
        "reduce_scatter",
        "pbroadcast",
    }
)

# Full reductions: outputs identical on every rank of the reduced axes.
REDUCTION_PRIMS = frozenset(
    {"psum", "psum_invariant", "pmax", "pmin", "pmean"}
)

# Collectives whose OUTPUT is identical on every rank of the reduced
# axes — the ancestry that makes a cond predicate "globally agreed".
REPLICATING_PRIMS = REDUCTION_PRIMS | frozenset(
    {"all_gather", "all_gather_invariant", "pbroadcast"}
)

# Per-rank-varying sources: outputs vary over the communicated axes.
VARYING_PRIMS = frozenset(
    {"axis_index", "pshuffle", "all_to_all", "psum_scatter",
     "reduce_scatter"}
)

# Call-like HOFs whose body invars map 1:1 onto eqn invars (jax 0.9
# names: a nested ``jax.jit`` is ``jit``, ``jax.checkpoint`` is ``remat2``).
CALL_PRIMS = frozenset(
    {"jit", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call"}
)


def collective_axes(eqn) -> Tuple[str, ...]:
    """The mesh axes a collective eqn communicates over (``axes`` for the
    reductions, ``axis_name`` for ppermute/all_to_all), normalized."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


def _sig_entry(eqn) -> str:
    shapes = ",".join(
        f"{np.dtype(v.aval.dtype).name}[{'x'.join(map(str, v.aval.shape))}]"
        for v in eqn.invars
        if hasattr(getattr(v, "aval", None), "shape")
    )
    return f"{eqn.primitive.name}@({','.join(collective_axes(eqn))}) {shapes}"


def collective_signature(jaxpr) -> Tuple[str, ...]:
    """Ordered collective schedule of a (sub)jaxpr: one entry per
    collective eqn, in depth-first trace order — primitive + axes +
    operand shape/dtype. Two branches with equal signatures issue the
    same wire schedule on every rank."""
    return tuple(
        _sig_entry(e)
        for e in walk_eqns(jaxpr)
        if e.primitive.name in COLLECTIVE_PRIMS
    )


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")  # core.Literal; Vars have no .val


# ---------------------------------------------------------------------
# findings + recorded program points
# ---------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardFinding:
    """One S-rule violation in one traced program. Carries the same
    surface as gridlint's Finding (rule/path/symbol/message +
    ``baseline_key``) so the suppression-baseline machinery and the
    shared SARIF/github formatters apply unchanged."""

    rule: str
    program: str
    message: str
    path: str = "mpi_grid_redistribute_tpu/analysis/shardcheck.py"
    line: int = 1

    @property
    def symbol(self) -> str:
        return self.program

    def baseline_key(self) -> Tuple[str, str, str, str]:
        return (self.rule, self.path, self.program, self.message)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return f"<{self.program}>: {self.rule}: {self.message}"


VarySet = FrozenSet[str]


@dataclasses.dataclass(frozen=True)
class CondSite:
    """One lax.cond/switch: its predicate's vary-set and each branch's
    ordered collective signature (what J001 judges)."""

    pred_vary: VarySet
    signatures: Tuple[Tuple[str, ...], ...]


@dataclasses.dataclass(frozen=True)
class ReductionSite:
    """One full reduction whose operand was already replicated on some
    reduced axis (what S002 judges)."""

    prim: str
    axes: Tuple[str, ...]
    redundant_axes: Tuple[str, ...]
    operand_bytes: int


@dataclasses.dataclass(frozen=True)
class EscapeSite:
    """One varying value reaching a host-visible surface. ``kind`` is
    ``replicated_out`` (a shard_map output declared P() — S001),
    ``scan_ys`` or ``output`` (S003)."""

    kind: str
    index: int
    axes: Tuple[str, ...]


@dataclasses.dataclass
class ShardReport:
    """Everything one :func:`analyze` pass inferred about a program."""

    out_vary: List[VarySet]
    conds: List[CondSite]
    reductions: List[ReductionSite]
    escapes: List[EscapeSite]
    var_vary: Dict[object, VarySet]


# ---------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------


class _VaryInterp:
    """Forward vary-set propagation over one traced program.

    ``_scope`` is the set of mesh axes currently bound (empty at host
    level, the full mesh inside a shard_map body); ``_axis_sizes`` maps
    in-scope axis names to their sizes (for the ppermute full-perm
    test). All recorded sites are keyed by ``id(eqn)`` so fixpoint
    re-walks of scan/while bodies overwrite rather than duplicate —
    vary-sets only grow, so the final walk's verdict is the sound one.
    """

    def __init__(self):
        self._scope: VarySet = frozenset()
        self._axis_sizes: Dict[str, int] = {}
        self.var_vary: Dict[object, VarySet] = {}
        self._conds: Dict[int, CondSite] = {}
        self._reductions: Dict[int, ReductionSite] = {}
        self._escapes: Dict[Tuple, EscapeSite] = {}

    # -- core walk ----------------------------------------------------

    def _jaxpr(self, jaxpr, in_vary: List[VarySet]) -> List[VarySet]:
        env: Dict[object, VarySet] = {}
        for v, s in zip(jaxpr.invars, in_vary):
            env[v] = frozenset(s)
        for v in jaxpr.constvars:
            env[v] = frozenset()  # trace-time constants: replicated

        def get(atom) -> VarySet:
            if _is_literal(atom):
                return frozenset()
            # an unbound var would mean a malformed jaxpr; read it as
            # varying on every in-scope axis rather than crashing
            return env.get(atom, frozenset(self._scope))

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [get(a) for a in eqn.invars]
            if name == "cond":
                outs = self._cond(eqn, ins)
            elif name == "scan":
                outs = self._scan(eqn, ins)
            elif name == "while":
                outs = self._while(eqn, ins)
            elif name == "shard_map":
                outs = self._shard_map(eqn, ins)
            elif name in CALL_PRIMS:
                subs = [jaxpr_of(s) for s in subjaxprs(eqn)]
                if subs and len(subs[0].invars) == len(eqn.invars):
                    outs = self._jaxpr(subs[0], ins)
                    for extra in subs[1:]:
                        self._opaque_body(extra)
                else:
                    outs = self._opaque(eqn)
            elif name in REDUCTION_PRIMS:
                outs = self._reduction(eqn, ins)
            elif name in REPLICATING_PRIMS:
                joined = frozenset().union(*ins) if ins else frozenset()
                outs = [joined - set(collective_axes(eqn))] * len(eqn.outvars)
            elif name == "ppermute":
                outs = self._ppermute(eqn, ins)
            elif name in VARYING_PRIMS:
                joined = frozenset().union(*ins) if ins else frozenset()
                taint = joined | set(collective_axes(eqn))
                outs = [taint] * len(eqn.outvars)
            else:
                subs = list(subjaxprs(eqn))
                if subs:
                    outs = self._opaque(eqn)
                else:
                    # elementwise/default: join of the inputs
                    joined = frozenset().union(*ins) if ins else frozenset()
                    outs = [joined] * len(eqn.outvars)
            for v, s in zip(eqn.outvars, outs):
                env[v] = s
                self.var_vary[v] = s
        return [get(v) for v in jaxpr.outvars]

    def _opaque_body(self, sub) -> None:
        s = jaxpr_of(sub)
        self._jaxpr(s, [frozenset(self._scope)] * len(s.invars))

    def _opaque(self, eqn) -> List[VarySet]:
        for sub in subjaxprs(eqn):
            self._opaque_body(sub)
        return [frozenset(self._scope)] * len(eqn.outvars)

    # -- collectives --------------------------------------------------

    def _reduction(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        axes = collective_axes(eqn)
        joined = frozenset().union(*ins) if ins else frozenset()
        if eqn.params.get("axis_index_groups") is not None:
            # grouped reduction: replicated only within each group, and
            # group membership is rank-dependent — no axis is cleared
            return [joined] * len(eqn.outvars)
        redundant = tuple(
            sorted(a for a in axes if a in self._scope and a not in joined)
        )
        from mpi_grid_redistribute_tpu.analysis.progcheck import aval_bytes

        self._reductions[id(eqn)] = ReductionSite(
            prim=eqn.primitive.name,
            axes=axes,
            redundant_axes=redundant,
            operand_bytes=sum(aval_bytes(v.aval) for v in eqn.invars),
        )
        return [joined - set(axes)] * len(eqn.outvars)

    def _ppermute(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        joined = frozenset().union(*ins) if ins else frozenset()
        axes = collective_axes(eqn)
        size = 1
        for a in axes:
            if a not in self._axis_sizes:
                return [joined | set(axes)] * len(eqn.outvars)
            size *= int(self._axis_sizes[a])
        perm = eqn.params.get("perm") or ()
        srcs = {int(p[0]) for p in perm}
        dsts = {int(p[1]) for p in perm}
        full = (
            len(perm) == size
            and srcs == set(range(size))
            and dsts == set(range(size))
        )
        if full:
            # a full permutation (rotation, identity, ...) is
            # lattice-identity: a replicated operand stays replicated,
            # a varying one stays varying
            return [joined] * len(eqn.outvars)
        # partial perm: uncovered ranks receive zeros — rank-dependent
        return [joined | set(axes)] * len(eqn.outvars)

    # -- HOFs ---------------------------------------------------------

    def _cond(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        pred = ins[0]
        branches = branch_jaxprs(eqn)
        branch_outs = [self._jaxpr(b, list(ins[1:])) for b in branches]
        self._conds[id(eqn)] = CondSite(
            pred_vary=pred,
            signatures=tuple(collective_signature(b) for b in branches),
        )
        n_out = len(eqn.outvars)
        return [
            pred.union(*[bo[i] for bo in branch_outs])
            for i in range(n_out)
        ]

    def _scan(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        body = jaxpr_of(eqn.params["jaxpr"])
        nc = int(eqn.params["num_consts"])
        ncar = int(eqn.params["num_carry"])
        consts, carry, xs = ins[:nc], ins[nc : nc + ncar], ins[nc + ncar :]
        # union fixpoint: vary-sets only grow through the body, so this
        # terminates; the final walk sees the stable carry
        outs = [frozenset()] * len(body.outvars)
        for _ in range(64):
            outs = self._jaxpr(body, consts + carry + xs)
            new_carry = [c | o for c, o in zip(carry, outs[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        ys = outs[ncar:]
        if not self._scope:
            # host-level scan: its stacked ys are a host-visible surface
            for i, s in enumerate(ys):
                if s:
                    self._escapes[("scan_ys", id(eqn), i)] = EscapeSite(
                        "scan_ys", i, tuple(sorted(s))
                    )
        return carry + ys

    def _while(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        cond_j = jaxpr_of(eqn.params["cond_jaxpr"])
        body_j = jaxpr_of(eqn.params["body_jaxpr"])
        cn = int(eqn.params["cond_nconsts"])
        bn = int(eqn.params["body_nconsts"])
        cond_consts = ins[:cn]
        body_consts = ins[cn : cn + bn]
        carry = ins[cn + bn :]
        pred = frozenset()
        for _ in range(64):
            cond_outs = self._jaxpr(cond_j, cond_consts + carry)
            pred = cond_outs[0] if cond_outs else frozenset()
            outs = self._jaxpr(body_j, body_consts + carry)
            new_carry = [c | o for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        # a rank-varying predicate means rank-varying trip counts:
        # every carry leaves the loop rank-dependent
        return [c | pred for c in carry]

    def _shard_map(self, eqn, ins: List[VarySet]) -> List[VarySet]:
        body = jaxpr_of(eqn.params["jaxpr"])
        mesh = eqn.params["mesh"]
        in_specs = eqn.params["in_specs"]
        out_specs = eqn.params["out_specs"]
        if len(body.invars) != len(eqn.invars):
            return self._opaque(eqn)
        axis_names = tuple(str(a) for a in mesh.axis_names)
        sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        body_in = []
        for spec, s in zip(in_specs, ins):
            partitioned = _spec_axes(spec)
            # a partitioned dim makes the shard rank-dependent; an empty
            # spec (P()) is a replicated broadcast — the operand's own
            # taint rides along either way
            body_in.append(s | partitioned)
        saved = (self._scope, self._axis_sizes)
        self._scope = frozenset(axis_names)
        self._axis_sizes = {**self._axis_sizes, **sizes}
        body_out = self._jaxpr(body, body_in)
        self._scope, self._axis_sizes = saved
        outs: List[VarySet] = []
        for i, (spec, s) in enumerate(zip(out_specs, body_out)):
            partitioned = _spec_axes(spec)
            resid = s - partitioned
            if not partitioned and s:
                # declared fully replicated (P()) but provably varying:
                # S001's program point. Reported here, so the residual
                # taint does not double-fire downstream rules.
                self._escapes[("replicated_out", id(eqn), i)] = EscapeSite(
                    "replicated_out", i, tuple(sorted(s))
                )
                resid = frozenset()
            outs.append(resid)
        return outs


def _spec_axes(spec) -> VarySet:
    """Mesh axes a ``PartitionSpec`` partitions over; each entry is
    ``None``, one axis name or a tuple of them."""
    out = set()
    for entry in spec:
        if entry is None:
            continue
        out.update(entry if isinstance(entry, tuple) else (entry,))
    return frozenset(str(a) for a in out)


def analyze(closed) -> ShardReport:
    """Run the vary-set interpreter over one traced program and return
    the full report: per-var vary-sets plus the recorded cond,
    redundant-reduction and escape sites."""
    interp = _VaryInterp()
    j = jaxpr_of(closed)
    out = interp._jaxpr(j, [frozenset()] * len(j.invars))
    for i, s in enumerate(out):
        if s:
            interp._escapes[("output", 0, i)] = EscapeSite(
                "output", i, tuple(sorted(s))
            )
    return ShardReport(
        out_vary=out,
        conds=list(interp._conds.values()),
        reductions=[
            r for r in interp._reductions.values() if r.redundant_axes
        ],
        escapes=sorted(
            interp._escapes.values(),
            key=lambda e: (e.kind, e.index, e.axes),
        ),
        var_vary=interp.var_vary,
    )


# ---------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------


def run_shardcheck(
    programs: Optional[Dict[str, ProgramSpec]] = None,
    rules: Optional[Iterable[str]] = None,
) -> Tuple[List[ShardFinding], Dict[str, dict]]:
    """Trace every program, run the interpreter and the S-rules.
    Returns ``(findings, wires)`` — wires are the S004 per-axis wire
    attributions; the CALLER gates them against the committed baseline
    (so ``--update-baseline`` can share one trace pass)."""
    from mpi_grid_redistribute_tpu.analysis import rules_shard

    programs = default_programs() if programs is None else programs
    wanted = set(rules) if rules else set(S_RULE_IDS)
    findings: List[ShardFinding] = []
    wires: Dict[str, dict] = {}
    for name in sorted(programs):
        spec = programs[name]
        closed = trace_program(spec)
        if wanted & {"S001", "S002", "S003"}:
            report = analyze(closed)
            if "S001" in wanted:
                findings.extend(rules_shard.check_s001(report, spec))
            if "S002" in wanted:
                findings.extend(rules_shard.check_s002(report, spec))
            if "S003" in wanted:
                findings.extend(rules_shard.check_s003(report, spec))
        if "S004" in wanted:
            wires[name] = rules_shard.wire_profile(closed)
    findings.sort(key=lambda f: (f.rule, f.program, f.message))
    return findings, wires


# ---------------------------------------------------------------------
# CLI (exit codes mirror gridlint: 0 clean, 1 findings, 2 usage)
# ---------------------------------------------------------------------


def _parser() -> argparse.ArgumentParser:
    from mpi_grid_redistribute_tpu.analysis.baseline import (
        progprofile_baseline_path,
        shardcheck_baseline_path,
    )

    p = argparse.ArgumentParser(
        prog="shardcheck",
        description="Sharding/replication abstract interpreter: traces "
        "the registered SPMD programs, infers per-mesh-axis vary-sets "
        "and checks invariants S001-S004.",
    )
    p.add_argument(
        "--format",
        choices=("text", "json", "sarif", "github"),
        default="text",
        help="output format",
    )
    p.add_argument(
        "--rules",
        default=None,
        metavar="S00x[,S00y]",
        help="comma-separated subset of rules to run",
    )
    p.add_argument(
        "--programs",
        default=None,
        metavar="NAME[,NAME]",
        help="comma-separated subset of registered programs",
    )
    p.add_argument(
        "--baseline",
        default=None,
        metavar="PATH",
        help="S004 wire-attribution baseline (default: "
        f"{progprofile_baseline_path()}, section 'wire_attribution')",
    )
    p.add_argument(
        "--suppressions",
        default=None,
        metavar="PATH",
        help="journal-suppression baseline for S001-S003 findings "
        f"(default: {shardcheck_baseline_path()})",
    )
    p.add_argument(
        "--no-suppressions",
        action="store_true",
        help="ignore the suppression baseline; report every finding",
    )
    p.add_argument(
        "--write-suppressions",
        action="store_true",
        help="write current S001-S003 findings to the suppression "
        "baseline and exit 0",
    )
    p.add_argument(
        "--check",
        action="store_true",
        help="CI mode: additionally fail on stale suppression entries "
        "and on wire-baseline entries for unregistered programs",
    )
    p.add_argument(
        "--update-baseline",
        action="store_true",
        help="write the current wire attributions to the baseline's "
        "wire_attribution section and exit 0",
    )
    p.add_argument(
        "--rtol",
        type=float,
        default=0.0,
        help="relative tolerance for S004 numeric drift (default 0: "
        "the static model is deterministic, any drift is a change)",
    )
    p.add_argument(
        "--list-rules", action="store_true", help="list rules and exit"
    )
    p.add_argument(
        "--list-programs",
        action="store_true",
        help="list registered programs and exit",
    )
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    from mpi_grid_redistribute_tpu.analysis import rules_shard, sarif
    from mpi_grid_redistribute_tpu.analysis.baseline import (
        load_baseline,
        load_wire_baseline,
        progprofile_baseline_path,
        shardcheck_baseline_path,
        split_baselined,
        write_baseline,
        write_wire_baseline,
    )

    args = _parser().parse_args(argv)

    if args.list_rules:
        for rid in S_RULE_IDS:
            print(f"{rid}  {rules_shard.RULE_DOCS[rid]}")
        return 0

    rules: Optional[List[str]] = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rules if r not in S_RULE_IDS]
        if unknown:
            print(
                f"shardcheck: unknown rule(s): {', '.join(unknown)} "
                f"(known: {', '.join(S_RULE_IDS)})",
                file=sys.stderr,
            )
            return 2

    programs = default_programs()
    if args.list_programs:
        for name in sorted(programs):
            spec = programs[name]
            print(f"{name}  [{spec.engine}/{spec.topology}]  {spec.description}")
        return 0
    if args.programs:
        wanted = [p.strip() for p in args.programs.split(",") if p.strip()]
        unknown = [p for p in wanted if p not in programs]
        if unknown:
            print(
                f"shardcheck: unknown program(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(programs))})",
                file=sys.stderr,
            )
            return 2
        programs = {n: programs[n] for n in wanted}

    findings, wires = run_shardcheck(programs, rules=rules)

    wire_path = args.baseline or progprofile_baseline_path()
    if args.update_baseline:
        write_wire_baseline(wire_path, wires)
        print(
            f"shardcheck: wrote {len(wires)} wire attribution(s) to "
            f"{wire_path}"
        )
        return 0

    supp_path = args.suppressions or shardcheck_baseline_path()
    if args.write_suppressions:
        write_baseline(
            supp_path,
            findings,
            justification="journal-suppressed at shardcheck introduction",
            comment=(
                "shardcheck suppression baseline: S001-S003 findings "
                "accepted as wire-cost journal entries (S002 especially "
                "— a redundant collective kept deliberately). Matching "
                "is (rule, path, program, message). Remove entries as "
                "the underlying schedule is fixed; never add entries to "
                "dodge a new finding without a justification."
            ),
        )
        print(
            f"shardcheck: wrote {len(findings)} suppression(s) to "
            f"{supp_path}"
        )
        return 0

    suppressed = (
        set() if args.no_suppressions else load_baseline(supp_path)
    )
    new, grandfathered = split_baselined(findings, suppressed)

    stale: List[tuple] = []
    if args.check and suppressed:
        matched = {f.baseline_key() for f in grandfathered}
        stale = sorted(suppressed - matched)

    if wires:  # S004 requested: gate against the committed baseline
        baseline = load_wire_baseline(wire_path)
        new.extend(
            rules_shard.compare_wire(
                wires,
                baseline,
                rtol=args.rtol,
                check_stale=args.check,
                partial=args.programs is not None,
            )
        )
        # ISSUE-19 acceptance gate: hierarchical DCN bytes must stay a
        # sliver of the flat sparse engine's cross-pod bytes (skipped
        # automatically when --programs leaves either side untraced).
        new.extend(rules_shard.check_dcn_ratio(wires))
        new.sort(key=lambda f: (f.rule, f.program, f.message))

    if args.format == "json":
        print(
            json.dumps(
                {
                    "findings": [f.to_dict() for f in new],
                    "suppressed": len(grandfathered),
                    "stale_suppressions": [list(k) for k in stale],
                    "programs": sorted(programs),
                    "wire_attribution": wires,
                },
                indent=2,
                sort_keys=True,
            )
        )
    elif args.format == "sarif":
        print(
            json.dumps(
                sarif.to_sarif(new, "shardcheck", rules_shard.RULE_DOCS),
                indent=2,
            )
        )
        for key in stale:
            print(
                f"stale suppression entry (code fixed? remove it): "
                f"{key[0]} [{key[2]}]",
                file=sys.stderr,
            )
    elif args.format == "github":
        for line in sarif.github_annotations(new):
            print(line)
        for key in stale:
            print(
                f"stale suppression entry (code fixed? remove it): "
                f"{key[0]} [{key[2]}]",
                file=sys.stderr,
            )
    else:
        for f in new:
            print(f.render())
        for key in stale:
            print(
                f"stale suppression entry (code fixed? remove it): "
                f"{key[0]} [{key[2]}]"
            )
        summary = (
            f"shardcheck: {len(new)} finding(s) over "
            f"{len(programs)} program(s)"
        )
        if grandfathered:
            summary += f", {len(grandfathered)} suppressed"
        if stale:
            summary += f", {len(stale)} stale suppression(s)"
        print(summary)

    return 1 if (new or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
