"""Probe: flat 64M single-key payload sort vs batched per-slab [V, n]
sort (the vrank-major deposit-key idea).

The MXU deposit's remaining dominant cost is the single-key unstable
payload sort at m = V*n rows (~179 ms at 67M, deposit.py docstring).
If cells are numbered VRANK-MAJOR (key = v*C + local_cell), every slab's
valid keys lie in [v*C, (v+1)*C), so sorting each slab INDEPENDENTLY
yields a stream whose valid keys are globally non-decreasing — exactly
what pallas_segdep needs (with first-chunk-from-min fix). A batched
[V, n] axis-sort is V independent n-row sorts: lower depth
(log^2 n vs log^2 m) and lane-friendlier.

Scan-length-differenced (utils/profiling), so compile and dispatch
cancel.

Usage: python scripts/microbench_slab_sort.py [V] [n]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_grid_redistribute_tpu.utils import profiling

V = int(sys.argv[1]) if len(sys.argv) > 1 else 64
n = int(sys.argv[2]) if len(sys.argv) > 2 else 1_048_576
m = V * n
C = 32768  # cells per vrank (128^3 / 64)

rng = np.random.default_rng(0)
key_flat = jnp.asarray(rng.integers(0, V * C, size=m, dtype=np.int32))
rel = [jnp.asarray(rng.random(m, dtype=np.float32)) for _ in range(3)]
mass = jnp.asarray(rng.random(m, dtype=np.float32))

# slab-local keys: each slab v gets keys in [v*C, (v+1)*C)
key_slab = (
    key_flat.reshape(V, n) % C
    + (jnp.arange(V, dtype=jnp.int32) * C)[:, None]
)


def make_loop_flat(S):
    @jax.jit
    def loop(key, r0, r1, r2, mass):
        def body(carry, _):
            k, a, b, c, w = carry
            s = jax.lax.sort((k, a, b, c, w), num_keys=1, is_stable=False)
            # feed the sorted payload back (xor keeps the key range) so
            # the scan cannot be collapsed across iterations
            k2 = s[0] ^ 1
            return (k2, s[1], s[2], s[3], s[4]), s[0][0]

        carry, outs = jax.lax.scan(
            body, (key, r0, r1, r2, mass), None, length=S
        )
        return outs

    return loop


def make_loop_slab(S):
    @jax.jit
    def loop(key2, r0, r1, r2, mass):
        ops = tuple(x.reshape(V, n) for x in (r0, r1, r2, mass))

        def body(carry, _):
            k, a, b, c, w = carry
            s = jax.lax.sort((k, a, b, c, w), num_keys=1, is_stable=False)
            k2 = s[0] ^ 1
            return (k2, s[1], s[2], s[3], s[4]), s[0][0, 0]

        carry, outs = jax.lax.scan(body, (key2,) + ops, None, length=S)
        return outs

    return loop


t_flat, _, _ = profiling.scan_time_per_step(
    make_loop_flat, (key_flat, *rel, mass), s1=2, s2=8
)
t_slab, _, _ = profiling.scan_time_per_step(
    make_loop_slab, (key_slab, *rel, mass), s1=2, s2=8
)
print(f"V={V} n={n} m={m}")
print(f"flat   sort ({m} rows, 5 operands): {t_flat * 1e3:8.2f} ms")
print(f"[V, n] sort ({V}x{n}, 5 operands):  {t_slab * 1e3:8.2f} ms")
