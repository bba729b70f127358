"""Software-pipelined resident macro-stepping (ISSUE 12).

:mod:`.resident` made the service loop device-resident; each scan
iteration still runs bin -> pack -> exchange -> unpack strictly in
order, so the exchange sits serialized against compute that does not
depend on it. :func:`make_pipelined_chunk_fn` builds the overlapped
sibling: the scan carry is DOUBLE-BUFFERED — it holds step k's issued
(in-flight) exchange payload alongside step k+1's entry state — and the
steady-state body issues step k+1's drift + binning + leaver-selection
BEFORE consuming step k's exchanged rows. On a chip the issued gather /
collective then overlaps the next step's routing sort; on CPU the win
is the cheaper schedule itself (one targeted landing scatter per step
instead of a full payload-carrying compaction sort — see README
"Pipelined stepping" for why CPU gains are modest).

The engine under the schedule is the two-phase vranks planar pair
(:func:`..parallel.migrate.vrank_exchange_two_phase_fn`, resolved via
:func:`..parallel.exchange.resolve_two_phase`): ``issue`` reads only
the destination key and the free-slot counts, ``land`` writes payload +
alive (+ the precomputed next-step key row, riding the SAME scatter —
the fused free-stack update means no second pass over landing rows).
Routing uses the same :func:`..ops.binning.rank_of_position_planar`
as the canonical planar engines and the drift is
:func:`..models.nbody.service_drift` bit-for-bit, so a committed chunk
(no drops, no backlog) reproduces the sequential engine's physics
exactly; any step with drops or backlog is reported in the scanned ys
and the driver discards + re-runs the chunk eagerly, exactly as for
sequential overflow.

Degrade contract (ISSUE 12): infeasible schedules degrade at BUILD time
to the sequential :func:`..service.resident.make_chunk_fn` — chunk < 2,
non-planar payload, ragged receive capacity, multi-device topology —
each journaled as an ``engine_resolved`` event with a "pipeline: ..."
reason (telemetry/SCHEMA.md). The remaining DYNAMIC hazard (a step
whose flow control could not grant every leaver — e.g. a fallback
flood filling the free slots) is handled by ONE ``lax.cond`` in the
scan body choosing between the pipelined and sequential orderings of
the same two kernels; the two branches are bit-identical by
construction (landing commutes with the elementwise drift column by
column), so the cond is a scheduling decision, never a numerics one,
and ``stats.pipeline`` journals which branch each step armed.

The macro body is ``# gridlint: resident-path`` like the sequential
one: G009 statically rejects host syncs inside it, and progcheck's
J002/J003 walk the traced program (registered as the
``pipeline_macro_step`` entry; the ``_progcheck_pipeline`` marker below
survives jit on ``.__wrapped__``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from mpi_grid_redistribute_tpu import api
from mpi_grid_redistribute_tpu.models import nbody
from mpi_grid_redistribute_tpu.ops import binning, pack, statehealth
from mpi_grid_redistribute_tpu.telemetry.phases import traced_span
from mpi_grid_redistribute_tpu.parallel import exchange, migrate
from mpi_grid_redistribute_tpu.service import resident


def _drift_compatible(specs, ndim):
    """The pipelined engine drifts IN the fused planar layout (position
    rows + velocity rows bitcast back to f32), which needs the payload
    to be the service shape: float32 positions followed by a float32
    velocity field of the same width."""
    if specs is None or len(specs) < 2:
        return False
    f32 = np.dtype(np.float32)
    return (
        specs[0][1] == f32
        and specs[1][1] == f32
        and specs[0][2] == ndim
        and specs[1][2] == ndim
    )


def make_pipelined_chunk_fn(rd, dt, chunk, positions, *fields, unroll=8,
                            probes=None):
    """Build the software-pipelined jitted macro-step (ISSUE 12).

    Drop-in sibling of :func:`..service.resident.make_chunk_fn` — same
    arguments, same return ``(macro, cap, out_cap)``, same
    ``macro(pos, vel, ids, count) -> ((pos, vel, ids, count), ys)``
    contract with ``ys = {"stats": RedistributeStats[chunk, ...],
    "count": int32[chunk, R]}`` — so the driver swaps builders on the
    ``DriverConfig.pipeline`` knob and nothing downstream changes. The
    stats gain the ``pipeline`` leaf ([chunk, R] int32; 1 where the
    step's exchange armed for overlapped consumption).

    When :func:`..parallel.exchange.resolve_two_phase` degrades the
    schedule (chunk < 2, non-planar payload, ragged receive capacity,
    multi-device or multi-pod topology) this DELEGATES to the
    sequential builder —
    the returned macro is bit-exactly the sequential one, including its
    ``ResidentLayoutError`` on ragged carries — and the degradation is
    journaled. Because this builder runs under the driver's causal step
    context (``telemetry/context.py``), that ``engine_resolved`` event
    carries the active ``trace``/``ctx_*`` envelope fields and a ragged
    carry's ``ResidentLayoutError`` names the trace id, so build-time
    infeasibilities join against the step that forced the rebuild. ``unroll`` is forwarded on that path only; the pipelined
    scan keeps ``unroll=1`` (the double-buffered carry, not body
    replication, is its overlap mechanism).

    Differences visible to the caller on the armed path, by design:

    - the final arrays' ROW ORDER within each rank differs from the
      sequential engine's (resident-slot layout compacted once at the
      chunk boundary, vs a canonical re-pack every step). Particle SET,
      per-rank counts and drop accounting are preserved — the id audit
      (``service/elastic.py:particle_set``) is the equality the driver
      and tests assert.
    - steps whose flow control withholds movers report them as
      ``dropped_send`` (backlog) so the driver's discard + eager re-run
      path neutralizes the semantic difference; a committed chunk had
      every mover granted and nothing dropped in BOTH engines.
    - with ``probes`` armed, the NaN/OOB/moment scans run over the
      fused state at each step's ISSUE point (post-drift,
      pre-exchange; step k's arrivals are scanned at step k+1's
      issue), while ``live`` and the conservation ``residual`` come
      from the exact post-step counts ``_step_ys`` already computes —
      so the counters match the sequential probe exactly and a NaN
      row is detected at most one in-chunk step later. The ledger
      counts only ``dropped_recv`` here: this engine's
      ``dropped_send`` is withheld-but-resident backlog, not
      destroyed rows (``ops/statehealth.py``).
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    R = rd.nranks
    if positions.ndim != 2 or positions.shape[0] % R:
        raise ValueError(
            f"positions must be [R*n_local, ndim] over {R} ranks, "
            f"got {positions.shape}"
        )
    n_local = positions.shape[0] // R
    cap, out_cap = rd._capacities(n_local)
    for i, f in enumerate(fields):
        if f.dtype.itemsize > 4:
            # the two-phase carry and the sequential chunk body both hold
            # 32-bit rows; a wider field would be narrowed at the program
            # boundary, so refuse it here
            raise TypeError(
                f"the pipelined service path carries fields of at most 32 "
                f"bits: field {i} is {f.dtype}"
            )
    specs = api._planar_specs(positions, fields)
    # G004: the fused planar carry moves rows as 32-bit words — re-assert
    # the 4-byte contract at THIS call path (_planar_specs also admits
    # 8-byte values, as two words, which this carry does not).
    planar_ok = (
        specs is not None
        and all(np.dtype(s[1]).itemsize == 4 for s in specs)
        and rd.edges is None
        and _drift_compatible(specs, rd.domain.ndim)
    )
    n_dev = 1 if rd._vranks else int(rd.mesh.devices.size)
    handle = exchange.resolve_two_phase(
        rd.engine,
        chunk=chunk,
        planar_ok=planar_ok,
        ragged=out_cap != n_local,
        vranks=rd._vranks,
        n_devices=n_dev,
        n_pods=rd.n_pods,
        build=lambda: migrate.vrank_exchange_two_phase_fn(
            rd.domain, rd.grid, n_local, ndim=rd.domain.ndim
        ),
        recorder=rd.telemetry,
    )
    if not handle.armed:
        return resident.make_chunk_fn(
            rd, dt, chunk, positions, *fields, unroll=unroll,
            probes=probes,
        )
    tp = handle.bundle
    V, n = tp.vranks, tp.n_local
    D = rd.domain.ndim
    KP = sum(s[2] for s in specs)  # payload rows (alive row rides last)
    dt = float(dt)
    armed = probes is not None and probes.armed

    def _probe(T, count, live0, cum):
        """Step summary from the fused planar state at issue time:
        positions/velocities bitcast back to f32 rows, liveness from
        the alive row, the exact end-of-step live total from the ys
        ``count`` the caller just computed."""
        p = lax.bitcast_convert_type(T[:D], jnp.float32).T
        v = lax.bitcast_convert_type(T[D : 2 * D], jnp.float32).T
        return statehealth.summarize_masked(
            p, v, T[-1] > 0, jnp.sum(count), live0, cum,
            probes.lo, probes.hi, probes.tier,
        )

    def _drift(fused):
        """Drift the planar matrix in place of layout: position rows
        [0, D) advanced by velocity rows [D, 2D), elementwise — the
        exact :func:`..models.nbody.service_drift` arithmetic, so the
        result is bit-identical to drifting the row-major arrays.
        Works on ``[K, m]`` and ``[K, V, n]`` alike."""
        p = lax.bitcast_convert_type(fused[:D], jnp.float32)
        v = lax.bitcast_convert_type(fused[D : 2 * D], jnp.float32)
        p2 = nbody.service_drift(p, v, dt)
        return jnp.concatenate(
            [lax.bitcast_convert_type(p2, jnp.int32), fused[D:]], axis=0
        )

    def _step_ys(plan, n_free):
        """Every per-step observable is computable at ISSUE time (the
        landing is deterministic given the plan and the free counts),
        which is what lets the prologue emit step 1's ys and iteration
        j emit step j+1's — the ys stream is step-ordered even though
        landings trail by one iteration."""
        n_pop = jnp.clip(plan.n_in - plan.n_sent, 0, n_free)
        n_push = jnp.maximum(plan.n_sent - plan.n_in, 0)
        nf_after = n_free - n_pop + n_push
        count = (n - nf_after).astype(jnp.int32)
        dropped_recv = jnp.maximum(
            plan.n_in - plan.n_sent - n_free, 0
        ).astype(jnp.int32)
        live = n - n_free
        stay = live - jnp.sum(plan.desired, axis=1)
        sc = plan.allowed + jnp.diag(stay + plan.backlog)
        feasible = jnp.sum(plan.backlog) == 0
        stats = exchange.RedistributeStats(
            send_counts=sc.astype(jnp.int32),
            recv_counts=sc.T.astype(jnp.int32),
            dropped_send=plan.backlog.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=jnp.max(plan.desired, axis=1).astype(
                jnp.int32
            ),
            pipeline=jnp.broadcast_to(
                feasible.astype(jnp.int32), (V,)
            ),
        )
        return {"stats": stats, "count": count}, feasible

    def _issue_tail(T, nf):
        """Shared tail of prologue and scan body: put the CURRENT
        step's exchange in flight against the freshly drifted state."""
        key = tp.bin_key(T)
        plan = tp.issue(key, nf)
        arr = pack.gather_plan_cols(T, plan.arr_plan)
        ys, feasible = _step_ys(plan, nf)
        return plan, arr, ys, feasible

    def _pipe(operand):
        """Pipelined ordering: step k+1's drift + binning are issued
        BEFORE step k's exchanged rows are consumed; the arrival
        payload is drifted in flight and its next-step key row lands
        through the same single scatter (no second pass)."""
        T, stack, nf, arr, vac, ns, ni = operand
        U = _drift(T)
        key_u = tp.bin_key(U)  # step k+1 binning, BEFORE the landing
        arr_u = _drift(arr)
        pos_a = lax.bitcast_convert_type(
            arr_u[:D], jnp.float32
        ).transpose(1, 0, 2)  # [V, D, n] — components on axis -2
        dest_a = binning.rank_of_position_planar(
            pos_a, rd.domain, rd.grid
        )  # [V, n]; block v IS the destination vrank
        alive_a = arr_u[-1] > 0
        me = jnp.arange(V, dtype=jnp.int32)[:, None]
        key_a = jnp.where(
            alive_a & (dest_a != me), dest_a, V
        ).astype(jnp.int32)
        aug = jnp.concatenate(
            [U, key_u.reshape(1, V * n)], axis=0
        )
        arr_aug = jnp.concatenate([arr_u, key_a[None]], axis=0)
        aug2, stack2, nf2, _ = tp.land(
            aug, stack, nf, arr_aug, vac, ns, ni
        )
        T2 = aug2[: KP + 1]
        alive2 = T2[-1] > 0
        key2 = jnp.where(alive2, aug2[KP + 1], V).astype(
            jnp.int32
        ).reshape(V, n)
        return T2, stack2, nf2, key2

    def _seq(operand):
        """Sequential ordering of the SAME two kernels: consume step
        k's exchange first, then drift + bin step k+1. Bit-identical to
        :func:`_pipe` (the landing scatter commutes with the
        elementwise drift, column by column), so the cond never changes
        numerics — it preserves the sequential SCHEDULE when the flow
        control withheld movers (their next-step key must be recomputed
        from state, which is exactly what this branch does)."""
        T, stack, nf, arr, vac, ns, ni = operand
        T1, stack2, nf2, _ = tp.land(T, stack, nf, arr, vac, ns, ni)
        T2 = _drift(T1)
        key2 = tp.bin_key(T2)
        return T2, stack2, nf2, key2

    # gridlint: resident-path
    def macro(pos, vel, ids, count):
        with traced_span("pipe:enter"):
            fused_p = api._fuse_planar(
                pos, (vel, ids), V, n, specs, stacked=False
            )
            gcol = jnp.arange(V * n, dtype=jnp.int32)
            alive0 = ((gcol % n) < count[gcol // n]).astype(jnp.int32)
            work = jnp.concatenate([fused_p, alive0[None]], axis=0)
            st = migrate.init_state(work, vranks=V, batched=True)
            live0 = jnp.sum(count).astype(jnp.int32)
            # prologue: step 1's drift (nothing in flight yet)
            T = _drift(st.fused)
        with traced_span("pipe:issue"):
            plan, arr, ys1, feas = _issue_tail(T, st.n_free)
        cum0 = jnp.int32(0)
        if armed:
            cum0 = statehealth.step_dropped(
                ys1["stats"], pipelined=True
            )
            ys1["probe"] = _probe(T, ys1["count"], live0, cum0)

        def body(carry, _):
            if armed:
                T, stack, nf, arr, vac, ns, ni, feas, cum = carry
            else:
                T, stack, nf, arr, vac, ns, ni, feas = carry
            with traced_span("pipe:land+drift"):
                T2, stack2, nf2, key2 = lax.cond(
                    feas,
                    _pipe,
                    _seq,
                    (T, stack, nf, arr, vac, ns, ni),
                )
            with traced_span("pipe:issue"):
                plan2 = tp.issue(key2, nf2)
                arr2 = pack.gather_plan_cols(T2, plan2.arr_plan)
                ys, feas2 = _step_ys(plan2, nf2)
            carry2 = (
                T2, stack2, nf2, arr2,
                plan2.vacated, plan2.n_sent, plan2.n_in, feas2,
            )
            if armed:
                with traced_span("pipe:probe"):
                    cum = cum + statehealth.step_dropped(
                        ys["stats"], pipelined=True
                    )
                    ys["probe"] = _probe(T2, ys["count"], live0, cum)
                carry2 = carry2 + (cum,)
            return carry2, ys

        carry = (
            T, st.free_stack, st.n_free, arr,
            plan.vacated, plan.n_sent, plan.n_in, feas,
        )
        if armed:
            carry = carry + (cum0,)
        carry, ys_rest = lax.scan(
            body, carry, None, length=chunk - 1, unroll=1
        )
        with traced_span("pipe:exit"):
            ys = jax.tree.map(
                lambda a, b: jnp.concatenate([a[None], b], axis=0),
                ys1,
                ys_rest,
            )
            # epilogue: land step `chunk` (already drifted at issue time —
            # no further drift) and compact the resident slots once
            T, stack, nf, arr, vac, ns, ni = carry[:7]
            Tf, _, _, _ = tp.land(T, stack, nf, arr, vac, ns, ni)
            alive = (Tf[-1] > 0).reshape(V, n)
            perm = jnp.argsort(
                jnp.where(alive, jnp.int32(0), jnp.int32(1)),
                axis=1,
                stable=True,
            ).astype(jnp.int32)
            gidx = (
                jnp.arange(V, dtype=jnp.int32)[:, None] * n + perm
            ).reshape(-1)
            compact = jnp.take(Tf, gidx, axis=1)
            count_f = jnp.sum(alive, axis=1).astype(jnp.int32)
            pad = (
                jnp.arange(n, dtype=jnp.int32)[None, :] < count_f[:, None]
            ).reshape(-1)
            compact = jnp.where(pad[None, :], compact, 0)
            pos_f, fields_f = api._unfuse_planar(
                compact[:KP], specs, V, n, stacked=False
            )
            vel_f, ids_f = fields_f
        return (pos_f, vel_f, ids_f, count_f), ys

    # progcheck walks this program via the registry entry; both markers
    # survive jit (on `.__wrapped__`): `_progcheck_resident` keeps the
    # J002 resident-purity contract applied, `_progcheck_pipeline`
    # asserts the registry traced the genuine pipelined program.
    macro._progcheck_resident = True
    macro._progcheck_pipeline = True
    return jax.jit(macro), cap, out_cap
