#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 benchmark/control.py --workload drift8v.steady \\
        --program-seeds 12 --control-seeds 3 --seconds 2

For each program seed, one run of the cell at its own size and load
(set-up, a short window, the check); then for each control seed the
same run with the plain NumPy reference, computed in bfloat16, put in
the program's place. Every number compared is printed per run: the
program's should read 0 and the control's should not. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import generators, harness, manifest, reference  # noqa: E402


def control_patch(work) -> None:
    """Put the bfloat16 reference in place of the compiled program."""
    import jax
    import numpy as np

    geom, nb, D = work.geom, work.n_blocks, work.shapes["D"]
    steps = work.steps_per_call
    sharding = work.state[0].sharding

    def program(pos_p, vel_p, alive):
        pos = generators.planar_to_rows(np.asarray(pos_p), D, nb)
        vel = generators.planar_to_rows(np.asarray(vel_p), D, nb)
        p, v, a, sent = reference.call(
            geom, pos, vel, np.asarray(alive, bool), steps, bf16=True)
        R = len(a) // geom.n_local
        counts = np.zeros((steps, R), np.int32)
        counts[0, 0] = sent
        stats = work.Stats(counts, counts, np.zeros_like(counts))
        put = lambda x: jax.device_put(x, sharding)  # noqa: E731
        return (put(generators.rows_to_planar(p, nb)),
                put(generators.rows_to_planar(v, nb)), put(a), stats)

    work.program = program


def main(argv=None) -> int:
    t_all = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--program-seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = p.parse_args(argv)
    cell = manifest.resolve(args.workload)
    plan = ([("program", args.first_seed + i)
             for i in range(args.program_seeds)]
            + [("control", args.first_seed + 1000 + i)
               for i in range(args.control_seeds)])
    for kind, seed in plan:
        t0 = time.perf_counter()
        try:
            res = harness.run(
                cell, seed, args.seconds, False, t0=t0,
                patch=control_patch if kind == "control" else None)
        except harness.NoChip as e:
            harness.log(f"control: {e}")
            return 3
        print(json.dumps({
            "kind": kind, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "run_s": time.perf_counter() - t0,
        }), flush=True)
    harness.log(f"control: all runs in {time.perf_counter() - t_all:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
