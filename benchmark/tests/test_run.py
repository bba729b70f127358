"""The harness end to end on the CPU at small sizes: a sound run reads
correct, and each fault of the timed path, and the control, read not
correct. The look for a chip is skipped here (``require_chip=False``)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import control, generators, harness, manifest, reference

# slots per rank small enough for the CPU, large enough that flow control
# holds no migrant back over a short window
SMALL = {"drift8v.steady": 4096, "drift4c.steady": 32768}


def _cell(name):
    cell = manifest.resolve(name)
    cell.config["rank_slots"] = SMALL[name]
    return cell


def _run(name, patch=None, seconds=0.3):
    return harness.run(_cell(name), 2**31 + 11, seconds, False,
                       t0=time.perf_counter(), require_chip=False,
                       patch=patch)


def _host(work, arrays):
    D, nb = work.shapes["D"], work.n_blocks
    pos, vel, alive = (np.asarray(a) for a in arrays[:3])
    return (generators.planar_to_rows(pos, D, nb),
            generators.planar_to_rows(vel, D, nb), alive.astype(bool))


def _device(work, pos, vel, alive):
    import jax

    sh = work.state[0].sharding
    nb = work.n_blocks
    return tuple(jax.device_put(x, sh) for x in (
        generators.rows_to_planar(pos, nb), generators.rows_to_planar(vel, nb),
        alive))


def _wrap(work, after):
    """The timed path, with ``after(work, inputs, outputs)`` breaking its
    outputs before they reach the caller."""
    real = work.program

    def program(*state):
        out = real(*state)
        return after(work, state, out)

    work.program = program


def unchanged(work, state, out):
    """A step that returns its state unchanged."""
    return tuple(state) + (out[3],)


def half_left_out(work, state, out):
    """Half of the batch left out: the upper half of every rank's slots
    keeps its input, as if those particles were never stepped."""
    n = work.geom.n_local
    ip, iv, ia = _host(work, state)
    op, ov, oa = _host(work, out)
    keep = (np.arange(len(ia)) % n) >= n // 2
    op[keep], ov[keep], oa[keep] = ip[keep], iv[keep], ia[keep]
    return _device(work, op, ov, oa) + (out[3],)


def exchange_left_out(work, state, out):
    """The exchange left out: every particle drifts where it sits."""
    ip, iv, ia = _host(work, state)
    live = np.flatnonzero(ia)
    ip = ip.copy()
    ip[live] = reference.drift(ip[live], iv[live], work.geom,
                               work.steps_per_call)
    return _device(work, ip, iv, ia) + (out[3],)


def answer_altered(work, state, out):
    """One answer altered where it is produced: a payload bit flipped."""
    op, ov, oa = _host(work, out)
    ov = ov.copy()
    ov.view(np.uint32)[np.flatnonzero(oa)[0], 0] ^= 1
    return _device(work, op, ov, oa) + (out[3],)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_a_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"particles_per_s_per_chip", "call_p95_ms",
                                   "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == _cell(name).chips


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("fault", [unchanged, half_left_out,
                                   exchange_left_out, answer_altered],
                         ids=lambda f: getattr(f, "__name__", str(f)))
def test_a_broken_timed_path_is_not_correct(name, fault):
    res = _run(name, patch=lambda w: _wrap(w, fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_the_control_is_not_correct(name):
    res = _run(name, patch=control.control_patch)
    assert not res["correct"]
    assert res["checks"]["last_call_rows_wrong"]["value"] > 0


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    root = manifest.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "drift8v.steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_result_line_is_json_with_checks_last(capsys):
    res = _run("drift8v.steady", seconds=0.1)
    line = json.dumps(res)
    assert json.loads(line)["checks"]["rows_lost"] == {"value": 0, "limit": 0}
