"""Kill/restore smoke of the service driver, on the CPU.

SIGKILL the service driver mid-run after >= 2 committed snapshots, resume
it from the latest valid snapshot in a fresh process, and byte-compare
the final state against an uninterrupted run of the same config — the
kill-anywhere/restore-bit-identical contract of `service/driver.py` on
real subprocesses, on an 8-device virtual CPU mesh:

    python scripts/pod_smoke.py --kill-restore

The chip phases this script used to hold (all_to_all, migrate loop and
halo exchange across real chips) live in ``chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def kill_restore(steps: int = 40, n_local: int = 2048,
                 snapshot_every: int = 4) -> None:
    """SIGKILL the service driver mid-run; prove bit-identical resume.

    Three subprocesses on the forced-CPU 8-device mesh: a victim run
    killed with SIGKILL once >= 2 snapshots have committed, a resume run
    restoring from the latest valid snapshot in the same directory, and
    an uninterrupted reference run — resume and reference must produce
    byte-identical final state (pos/vel/count) at the same step.
    """
    import json
    import shutil
    import signal
    import subprocess
    import tempfile
    import time

    # host-only in the parent: snapshot inspection needs numpy + json,
    # never jax — the children own the devices
    from mpi_grid_redistribute_tpu.utils import checkpoint

    root = tempfile.mkdtemp(prefix="pod_smoke_kr_")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    base = [
        sys.executable, "-m", "mpi_grid_redistribute_tpu.service",
        "--grid", "2,2,2", "--n-local", str(n_local),
        "--steps", str(steps), "--seed", "5",
        "--snapshot-every", str(snapshot_every),
    ]
    snaps = os.path.join(root, "snaps")
    try:
        # --- victim: paced so SIGKILL lands mid-run -------------------
        victim = subprocess.Popen(
            base + ["--snapshot-dir", snaps, "--step-sleep", "0.05"],
            env=env, stdout=subprocess.DEVNULL,
        )
        deadline = time.time() + 180
        while time.time() < deadline:
            if len(checkpoint.list_snapshots(snaps)) >= 2:
                break
            if victim.poll() is not None:
                break
            time.sleep(0.05)
        committed = len(checkpoint.list_snapshots(snaps))
        assert committed >= 2, (
            f"victim produced only {committed} snapshots before "
            f"{'exiting' if victim.poll() is not None else 'the deadline'}"
        )
        if victim.poll() is None:
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=30)
            print(
                f"victim: SIGKILLed after {committed} committed "
                f"snapshots (exit {victim.returncode})", flush=True,
            )
        else:
            print(
                "victim: WARNING — finished before the kill landed; "
                "still exercising restore-from-snapshot", flush=True,
            )

        # --- resume: restore from the latest valid snapshot -----------
        latest = checkpoint.load_latest(snaps)
        assert latest is not None, "no valid snapshot survived the kill"
        resumed_out = os.path.join(root, "resumed.npz")
        subprocess.run(
            base + ["--snapshot-dir", snaps, "--final-out", resumed_out],
            env=env, check=True, stdout=subprocess.DEVNULL,
        )
        print(
            f"resume: restored step {latest.manifest['step']} "
            f"({latest.skipped} invalid snapshot(s) skipped), "
            f"ran to step {steps}", flush=True,
        )

        # --- reference: the same config, never interrupted ------------
        ref_out = os.path.join(root, "ref.npz")
        subprocess.run(
            base + [
                "--snapshot-dir", os.path.join(root, "ref_snaps"),
                "--final-out", ref_out,
            ],
            env=env, check=True, stdout=subprocess.DEVNULL,
        )

        with np.load(resumed_out) as res, np.load(ref_out) as ref:
            assert int(res["step"]) == int(ref["step"]) == steps
            for name in ("pos", "vel", "count"):
                assert res[name].tobytes() == ref[name].tobytes(), (
                    f"resumed {name} differs from the uninterrupted run"
                )
        print(
            f"kill-restore: OK (resumed trajectory bit-identical to the "
            f"uninterrupted run at step {steps})", flush=True,
        )
        print("KILL-RESTORE PASSED", flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    import argparse

    _p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    _p.add_argument(
        "--kill-restore",
        action="store_true",
        required=True,
        help="run the kill/restore leg (subprocess SIGKILL + "
        "bit-identical resume on the CPU mesh)",
    )
    _p.parse_args()
    kill_restore()
