"""Attribution probe for the PLANAR halo at the config-6 shape: which of
the per-pass stages — selection predicate, packed-order sort, column
gather, or the roll/append tail — dominates the 36.8 ns/ghost cost.

Truncated variants (cumulative, scan-differenced; zero recv is fed to
later axes for truncated variants, so deltas are directional — the
full variant is the engine):

  A  predicate + counts per pass
  B  A + packed one-word order sort (pack._stable_order)
  C  B + K-row column gather + periodic wrap surgery (send built)
  D  full engine (roll + vmapped DUS appends) = halo.vrank_halo_planar_fn

Usage: python scripts/microbench_halo_stages.py [n_local]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops.pack import _stable_order, _take_rows
from mpi_grid_redistribute_tpu.parallel import halo as halo_lib
from mpi_grid_redistribute_tpu.bench import common
from mpi_grid_redistribute_tpu.utils import profiling

n_local = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 18
grid = ProcessGrid((2, 2, 2))
R = grid.nranks
domain = Domain(0.0, 1.0, periodic=True)
w_f = 0.1 * min(grid.cell_widths(domain))
pc, gc = halo_lib.default_capacities(domain, grid, w_f, n_local)
rng = np.random.default_rng(0)
pos, _, _ = common.uniform_state(grid.shape, n_local, 1.0, rng)
count = np.full((R,), n_local, np.int32)
fused0 = jnp.asarray(
    np.ascontiguousarray(
        pos.reshape(R, n_local, 3).transpose(0, 2, 1)
    ).view(np.int32)
)
count0 = jnp.asarray(count)


def truncated(fused, count, phase):
    """Copy of vrank_halo_planar_fn's loop cut after ``phase`` per pass."""
    widths, cell_w = halo_lib._validate_widths(domain, grid, w_f)
    H, G = pc, gc
    V = grid.nranks
    nd = 3
    fi = fused
    K, n = fi.shape[1], fi.shape[2]
    valid = jnp.arange(n, dtype=jnp.int32)[None, :] < count[:, None]
    ghost = jnp.zeros((V, K, G + H), jnp.int32)
    gcount = jnp.zeros((V,), jnp.int32)
    overflow = jnp.zeros((V,), jnp.int32)
    ranks = jnp.arange(V, dtype=jnp.int32)
    strides = grid.strides
    probe = jnp.int32(0)

    for a in range(nd):
        g = grid.shape[a]
        w = jnp.asarray(widths[a], jnp.float32)
        extent_a = jnp.asarray(domain.extent[a], jnp.float32)
        coord_idx = (ranks // strides[a]) % g
        lo_a = (
            jnp.asarray(domain.lo[a], jnp.float32)
            + coord_idx.astype(jnp.float32)
            * jnp.asarray(cell_w[a], jnp.float32)
        )
        hi_a = lo_a + jnp.asarray(cell_w[a], jnp.float32)
        cand = jnp.concatenate([fi, ghost[:, :, :G]], axis=2)
        cand_valid = jnp.concatenate(
            [
                valid,
                jnp.arange(G, dtype=jnp.int32)[None, :] < gcount[:, None],
            ],
            axis=1,
        )
        incoming = []
        for dirn in (1, -1):
            at_edge = coord_idx == (g - 1 if dirn == 1 else 0)

            def pass_one(c_v, cv_v, lo_v, hi_v, e_v):
                D_row = lax.bitcast_convert_type(c_v[a, :], jnp.float32)
                if dirn == 1:
                    mask = cv_v & (D_row >= hi_v - w)
                else:
                    mask = cv_v & (D_row < lo_v + w)
                cnt = jnp.sum(mask.astype(jnp.int32))
                send_cnt = jnp.minimum(cnt, H)
                if phase == 0:
                    return jnp.zeros((c_v.shape[0], H), jnp.int32), send_cnt
                order = _stable_order(jnp.logical_not(mask))
                if phase == 1:
                    return (
                        jnp.zeros((c_v.shape[0], H), jnp.int32)
                        .at[0, 0]
                        .set(order[0]),
                        send_cnt,
                    )
                take = _take_rows(order, H)
                slot_valid = jnp.arange(H, dtype=jnp.int32) < send_cnt
                send = jnp.where(
                    slot_valid[None, :], jnp.take(c_v, take, axis=1), 0
                )
                shift = jnp.where(
                    e_v & domain.periodic[a],
                    -jnp.asarray(dirn, jnp.float32) * extent_a,
                    jnp.asarray(0, jnp.float32),
                )
                row_a = lax.bitcast_convert_type(send[a, :], jnp.float32)
                row_a = jnp.where(slot_valid, row_a + shift, row_a)
                send = jnp.concatenate(
                    [
                        send[:a],
                        lax.bitcast_convert_type(row_a, jnp.int32)[None, :],
                        send[a + 1 :],
                    ],
                    axis=0,
                )
                return send, send_cnt

            send, send_cnt = jax.vmap(pass_one)(
                cand, cand_valid, lo_a, hi_a, at_edge
            )
            probe = probe + send[0, 0, 0] + send_cnt[0]
            if phase >= 3:
                recv = jnp.roll(
                    send.reshape(grid.shape + send.shape[1:]), dirn, axis=a
                ).reshape(send.shape)
                recv_cnt = jnp.roll(
                    send_cnt.reshape(grid.shape), dirn, axis=a
                ).reshape((V,))
                incoming.append((recv, recv_cnt))
        for recv, recv_cnt in incoming:
            ghost, gcount, overflow = jax.vmap(
                lambda gh_v, gc_v, ov_v, rc_v, rcnt_v: halo_lib._append_recv_cols(
                    gh_v, gc_v, ov_v, rc_v, rcnt_v, pc, gc
                )
            )(ghost, gcount, overflow, recv, recv_cnt)
    return probe + gcount[0] + ghost[0, 0, 0]


def make_loop(phase):
    def build(S):
        if phase == 4:
            fn = halo_lib.vrank_halo_planar_fn(domain, grid, w_f, pc, gc)

            @jax.jit
            def loop(fused, count):
                def body(carry, _):
                    f, c = carry
                    gh, gcnt, ov = fn(f, c)
                    f = f + (gh[0, 0, 0] + gcnt[0] + ov[0]).astype(
                        jnp.int32
                    ) * 0
                    return (f, c), gcnt[0]

                _, outs = lax.scan(body, (fused, count), None, length=S)
                return outs
        else:

            @jax.jit
            def loop(fused, count):
                def body(carry, _):
                    f, c = carry
                    p = truncated(f, c, phase)
                    f = f + p * 0
                    return (f, c), p

                _, outs = lax.scan(body, (fused, count), None, length=S)
                return outs

        return loop

    return build


print(f"V={R} n_local={n_local} pc={pc} gc={gc}")
for phase, name in [
    (0, "A predicate+counts"),
    (1, "B +packed sort"),
    (2, "C +gather+wrap"),
    (3, "D +roll+appends"),
    (4, "E full engine fn"),
]:
    t, _, _ = profiling.scan_time_per_step(
        make_loop(phase), (fused0, count0), s1=2, s2=8
    )
    print(f"{name:22s}: {t * 1e3:8.2f} ms")
