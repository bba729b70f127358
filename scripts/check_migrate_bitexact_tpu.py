"""On-chip bit-exactness check for the migrate engines' payload transport.

Round-4 context: the canonical planar engines were found (on the real
chip) to FLUSH denormal f32 bit patterns — any bitcast int32 < 2^23 —
to zero inside the pack gather at >= ~3k rows/shard; the fix moved their
transport to an int32 bitcast view. The migrate engines carry the same
kind of fused planar matrix with bitcast payloads (migrate.fuse_fields)
through gathers + all_to_all + the landing scatter. This script drives a
real drift loop with a bitcast-int id row on the actual device and
asserts the id SET survives bit-exactly, for each landing-scatter impl.

Run on the TPU (no flags needed): python scripts/check_migrate_bitexact_tpu.py
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning
from mpi_grid_redistribute_tpu.parallel import migrate, mesh as mesh_lib
from mpi_grid_redistribute_tpu.bench import common


def run(n_local: int = 32768, steps: int = 10, scatter_impl=None) -> bool:
    dom = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((2, 2, 2))
    V = vgrid.nranks
    rng = np.random.default_rng(7)
    pos, vel, _ = common.uniform_state(
        vgrid.shape, n_local, 1.0, rng,
        vel_scale=0.02 / 3 * 2.0 / np.asarray(vgrid.shape, np.float32),
    )
    m = V * n_local
    ids = np.arange(m, dtype=np.int32)  # all denormal f32 bit patterns
    fused = np.concatenate(
        [
            pos.T.astype(np.float32).view(np.int32),
            vel.T.astype(np.float32).view(np.int32),
            ids[None, :],
            np.ones((1, m), np.int32),
        ],
        axis=0,
    )  # [8, V*n] int32 transport (migrate.fuse_fields convention)
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])
    mig = migrate.shard_migrate_vranks_fn(
        dom, dev_grid, vgrid, capacity=max(256, n_local // 16),
        scatter_impl=scatter_impl,
    )
    D = 3

    axes = dev_grid.axis_names

    def shard_loop(fused):
        state = migrate.init_state(fused, vranks=V, batched=True)

        def _vary(x):
            missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
            return lax.pcast(x, missing, to="varying") if missing else x

        state = jax.tree.map(_vary, state)

        def body(state, _):
            f = state.fused
            pf = lax.bitcast_convert_type(f[:D, :], jnp.float32)
            vf = lax.bitcast_convert_type(f[D : 2 * D, :], jnp.float32)
            p = binning.wrap_periodic_planar(pf + vf, dom)
            f = jnp.concatenate(
                [lax.bitcast_convert_type(p, jnp.int32), f[D:, :]], axis=0
            )
            state, stats = mig(state._replace(fused=f))
            return state, stats.backlog

        state, backlog = lax.scan(body, state, None, length=steps)
        return state.fused, backlog

    spec = P()
    out = jax.jit(
        shard_map(
            shard_loop, mesh=mesh, in_specs=(spec,),
            out_specs=(spec, spec), check_vma=False,
        )
    )(jnp.asarray(fused))
    f_out = np.asarray(out[0])
    alive = f_out[-1, :] > 0
    got = f_out[6, alive]
    ok_count = alive.sum() == m
    ok_ids = np.array_equal(np.sort(got), ids)
    impl = scatter_impl or "default"
    n_zero = int((got == 0).sum())
    print(
        f"scatter={impl}: alive {alive.sum()}/{m}, id set exact: {ok_ids}"
        + ("" if ok_ids else f" ({n_zero} zeros, {m - len(set(got.tolist()))} dups)")
    )
    return ok_count and ok_ids


if __name__ == "__main__":
    ok = True
    for impl in (None, "xla"):
        ok &= run(scatter_impl=impl)
    print("PASS" if ok else "FAIL")
