#!/usr/bin/env python3
"""The control of ``catalog8v.oneshot``: the plain NumPy reference put in
the program's place with its ids narrowed to 32 bits, as a program that
canonicalizes every field to 32 bits would return them. The check must
read ``ids_wrong`` (and ``rows_wrong``) above 0.

    python3 benchmark/control_oneshot.py --seeds 1 --seconds 2

One run of the cell per seed, at its own size, with the control in the
program's place: set-up, a short window, the check. Every number
compared is printed per run. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import harness, manifest, reference_oneshot  # noqa: E402

WORKLOAD = "catalog8v.oneshot"


class Stats(NamedTuple):
    send_counts: np.ndarray


class Result(NamedTuple):
    positions: np.ndarray
    fields: tuple
    count: np.ndarray
    stats: Stats


def control_patch(work) -> None:
    """Put the reference, ids narrowed to int32, in the program's place."""
    geom = work.geom
    out_capacity = int(work.rd.out_capacity)

    def program(pos, vel, ids):
        narrow = ids.astype(np.int32).astype(np.int64)
        p, (v, i), counts = reference_oneshot.redistribute(
            geom, pos, (vel, narrow), out_capacity)
        R = len(counts)
        return Result(p, (v, i), counts,
                      Stats(np.diag(counts).reshape(R, R)))

    work.program = program


def main(argv=None) -> int:
    t_all = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seeds", type=int, default=1)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--first-seed", type=int, default=3_100_000_001)
    args = p.parse_args(argv)
    cell = manifest.resolve(WORKLOAD)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        try:
            res = harness.run(cell, seed, args.seconds, False, t0=t0,
                              patch=control_patch)
        except harness.NoChip as e:
            harness.log(f"control: {e}")
            return 3
        print(json.dumps({
            "kind": "control", "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "run_s": time.perf_counter() - t0,
        }), flush=True)
    harness.log(f"control: all runs in {time.perf_counter() - t_all:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
