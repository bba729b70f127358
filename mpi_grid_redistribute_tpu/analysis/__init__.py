"""gridlint — AST-based SPMD/JIT invariant checker for this repo.

The redistribute hot path's whole value proposition is that it compiles
to ONE static-shape SPMD program per (N, capacity) bucket with
collectives riding ICI (``parallel/exchange.py``, PAPER.md §7.6). The
invariants that make that true — no data-dependent shapes in jitted
code, no host syncs in hot paths, collectives issued unconditionally
and in program order inside ``shard_map`` bodies — were previously
enforced only by convention. This package enforces them as named,
suppressible static-analysis rules:

========  ==============================================================
G001      collectives inside ``shard_map`` bodies must not sit under
          data-dependent ``if``/``while``/``try`` (deadlock hazard) or
          inside ``lax.cond``/``lax.while_loop``/``lax.switch`` branch
          functions, and literal ``axis_name`` arguments must match an
          axis declared in a mesh construction.
G002      jit-boundary hygiene: no ``.item()``, ``jax.device_get``,
          ``np.asarray``/``np.array``, or ``int()``/``float()``/
          ``bool()`` on traced values inside jit-reachable functions.
G003      dynamic-shape escapes: ``jnp.nonzero``/``jnp.unique``/
          ``jnp.argwhere``/``jnp.flatnonzero`` and 1-arg ``jnp.where``
          without ``size=``, and boolean-mask indexing, in jitted code.
G004      planar-engine 32-bit word contract: ``fuse_fields`` /
          ``_fuse_planar`` call sites must be guarded by an
          ``.itemsize`` check like ``api.py``'s ``_planar_specs``
          (4-byte values, or 8-byte ones split into two words).
G005      Pallas kernel lint: every ``pl.pallas_call`` passes explicit
          ``grid`` and ``BlockSpec``s; kernels using ``pl.program_id``
          must bound-check derived indices.
G006      mover-sparse cost contract: functions marked with a
          ``# gridlint: fastpath-engine`` comment above their ``def``
          must not call sort-family ops or ``take``/``take_along_axis``
          with ``arange``/``iota``-derived indices — resident-scale
          work silently reverts the sparse engine to dense cost.
========  ==============================================================

Suppress a finding with a same-line comment ``# gridlint: disable=G00x``
(comma-separate several rules) or a whole file with
``# gridlint: disable-file=G00x``. Grandfathered findings live in the
committed baseline file ``analysis/gridlint_baseline.json``.

CLI: ``python scripts/gridlint.py [paths] [--format=json] [--check]``
(also ``--format=sarif``/``--format=github`` and ``--check-baseline``
for suppression hygiene).

The G-rules read SOURCE. Their semantic complement is **progcheck**
(``analysis/progcheck.py`` + ``analysis/rules_jaxpr.py``): J-rules
J000–J004 that trace the REAL programs with ``jax.make_jaxpr`` and
verify what was actually staged — collective-schedule consistency
across ``lax.cond`` branches (J001), no host syncs in resident-marked
programs (J002), the fast-path cost contracts (J003), and a static
wire/footprint profile gated against
``analysis/progprofile_baseline.json`` (J004). CLI:
``python scripts/progcheck.py --check`` (``make progcheck``).

The third family is **shardcheck** (``analysis/shardcheck.py`` +
``analysis/rules_shard.py``): a forward abstract interpreter that maps
every var of every traced program to the set of mesh axes it may vary
over, and S-rules S001–S004 on top — replicated-out_specs consistency
(S001), redundant collectives (S002, journal-suppressed via
``analysis/shardcheck_baseline.json``), varying-value escapes to
host-visible surfaces (S003), and a per-axis ICI-vs-DCN wire
attribution drift-gated against the ``wire_attribution`` section of
the shared profile baseline (S004). J001 consumes this pass for its
replication proof. CLI: ``python scripts/shardcheck.py --check``
(``make shardcheck``).

The fifth family is **racecheck** (``analysis/racecheck.py`` +
``analysis/rules_thread.py``): gridlint's pure-AST twin for the HOST
side of the service control plane. It infers the thread topology
(``threading.Thread`` targets with daemon/joined facts, ``http.server``
handler pools), a per-root call-graph closure, and a cross-thread
shared-state matrix with lock-held classification from ``with <lock>:``
scopes, then gates T-rules T001–T005 — unguarded cross-thread writes
(T001), lock-order cycles (T002), blocking calls under a lock (T003),
non-daemon/un-joined threads escaping ``# gridlint: service-path``
modules (T004), and journal mutation outside the declared
``# racecheck: recorder-writer`` thread (T005). Suppressions use
racecheck's OWN marker (``# racecheck: disable=T00x``); grandfathered
findings live in ``analysis/racecheck_baseline.json``. Its runtime twin
is ``telemetry/tsan.py`` (``ThreadAccessTracer``), which audits a live
recorder's lock discipline deterministically. CLI:
``python scripts/racecheck.py --check`` (``make racecheck``;
``--list-threads`` dumps the inferred topology).

The sixth family is **kernelcheck** (``analysis/kernelcheck.py`` +
``analysis/rules_kernel.py``): G005's semantic complement for the
Pallas kernels. Each shipped kernel has a registered case in the
``KERNELS`` registry (the K-family's ``PROGRAMS`` analogue); a
trace-time ``pl.pallas_call`` patch under ``jax.eval_shape`` captures
the REAL call sites' grid/BlockSpec/scratch/alias anatomy, then
K-rules K000–K005 gate — registry completeness (K000), index maps
provably in bounds over the full grid (K001), scatter write
coverage/overlap and the revisiting-output contract (K002), a
(sublane, lane)-padded VMEM live footprint vs the ~16 MiB/core budget
drift-gated against ``analysis/kernelcheck_baseline.json`` (K003),
lane-tiling legality (K004), and interpret-mode bit-identity against
each case's registered jnp/XLA reference (K005). Suppressions use
kernelcheck's OWN marker (``# kernelcheck: disable=K00x``). CLI:
``python scripts/kernelcheck.py --check`` (``make kernelcheck``);
``make check`` runs the ``ANALYZERS`` registry in
``scripts/check_all.py`` — all six analyzers, one merged SARIF file.

progcheck, shardcheck and kernelcheck are NOT imported here: this
package root must stay importable without jax (gridlint and the
baseline helpers run host-only), so pull them in explicitly via
``mpi_grid_redistribute_tpu.analysis.progcheck`` /
``mpi_grid_redistribute_tpu.analysis.shardcheck`` /
``mpi_grid_redistribute_tpu.analysis.kernelcheck``. racecheck
(``mpi_grid_redistribute_tpu.analysis.racecheck``) is jax-free like
gridlint but stays un-imported too — its rule registry only needs
loading when the T-rules actually run.
"""

from mpi_grid_redistribute_tpu.analysis.core import (
    Finding,
    Project,
    RULE_IDS,
    run_gridlint,
)
from mpi_grid_redistribute_tpu.analysis.baseline import (
    default_baseline_path,
    load_baseline,
    write_baseline,
)

__all__ = [
    "Finding",
    "Project",
    "RULE_IDS",
    "run_gridlint",
    "default_baseline_path",
    "load_baseline",
    "write_baseline",
]
