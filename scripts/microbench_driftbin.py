"""On-chip microbench + bit check of the fused drift+wrap+bin kernel
(ops/pallas_driftbin.py) vs the XLA chain it replaces.

Usage: python scripts/microbench_driftbin.py [n_per_vrank] [V]
       python scripts/microbench_driftbin.py 1048576 64   # north-star
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import pallas_driftbin
from mpi_grid_redistribute_tpu.utils import profiling


def near_cubic(V):
    shape = []
    rem = V
    for _ in range(3):
        s = int(round(rem ** (1.0 / (3 - len(shape)))))
        while rem % s:
            s += 1
        shape.append(s)
        rem //= s
    return tuple(shape)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2**20
    V = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    K = 7
    domain = Domain(0.0, 1.0, periodic=True)
    grid = ProcessGrid(near_cubic(V))
    m = V * n
    r = np.random.default_rng(0)
    pos = r.random((3, m), dtype=np.float32)
    vel = (r.random((3, m), dtype=np.float32) - 0.5).astype(np.float32)
    alive = (r.random((m,)) < 0.9).astype(np.int32)
    # hostile probes: NaN / inf / huge / negative positions in a corner
    pos[0, :64] = np.nan
    pos[1, 64:128] = np.inf
    pos[2, 128:192] = -np.inf
    pos[0, 192:256] = 3e38
    pos[1, 256:320] = -7.5
    flat = jnp.asarray(
        np.concatenate(
            [pos.view(np.int32), vel.view(np.int32), alive[None]], axis=0
        )
    )

    xla = jax.jit(
        lambda f: pallas_driftbin.drift_wrap_bin_xla(
            f, 0.05, domain, grid, V, V
        )
    )
    kern = jax.jit(
        lambda f: pallas_driftbin.drift_wrap_bin(
            f, 0.05, domain, grid, V, V
        )
    )
    f_x, k_x = jax.block_until_ready(xla(flat))
    f_p, k_p = jax.block_until_ready(kern(flat))
    # device-side comparison: fetching [K, 67M] buffers to the host
    # costs far more than two scalar counts
    mism = jax.jit(
        lambda a, b, c, d: (
            jnp.sum((a != b).astype(jnp.int32), axis=1),
            jnp.sum((c != d).astype(jnp.int32)),
        )
    )
    row_ne, key_ne = map(np.asarray, mism(f_x, f_p, k_x, k_p))
    print(f"platform: {jax.devices()[0].platform}  V={V} n={n} m={m}")
    print(f"bit-equal: state={row_ne.sum() == 0} key={key_ne == 0}")
    if row_ne.sum() or key_ne:
        print(f"  per-row mismatches: {row_ne}, key: {key_ne}")

    def mk_loop(fn):
        def make(S):
            @jax.jit
            def loop(f):
                def body(f, _):
                    f2, key = fn(f)
                    # fold key into the carry so nothing is DCE'd
                    return f2.at[0, 0].add(key[0, 0]), ()

                f, _ = jax.lax.scan(body, f, None, length=S)
                return f

            return loop

        return make

    for name, fn in (("xla", None), ("kernel", None)):
        f = (
            (lambda fl: pallas_driftbin.drift_wrap_bin_xla(
                fl, 0.05, domain, grid, V, V))
            if name == "xla"
            else (lambda fl: pallas_driftbin.drift_wrap_bin(
                fl, 0.05, domain, grid, V, V))
        )
        per, _, _ = profiling.scan_time_per_step(
            mk_loop(f), (flat,), s1=4, s2=16
        )
        gb = (2 * K + 1) * m * 4 / 1e9
        print(
            f"{name:7s}: {per*1e3:8.3f} ms/step  "
            f"({gb / per:6.1f} GB/s of 819 effective)"
        )


if __name__ == "__main__":
    main()
