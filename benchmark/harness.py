"""One run of one cell: set-up, a measured window, the check, the result.

The run finds the chip (and fails without one), builds the cell's
state from the seed through the traffic's driver, warms up every shape
the window uses, then measures a closed loop: one caller that issues
the next call when the previous one has returned. With ``trace`` the
window is a fixed number of calls under the profiler, and the per-layer
metrics are read from that trace; without, the window lasts
``seconds`` and the end-to-end metrics are read from the host clock.

The driver's interface (``drivers/<name>.py``): ``build(config,
traffic, seed, devices)`` returns a workload with

* ``call(span)``: one call, its outputs ready and its stats on the host;
  ``span(name)`` opens a host span in a traced window;
* ``program``: the compiled program the calls drive (a test may put a
  broken one in its place);
* ``units_per_call``, ``steps_per_call``, ``shapes``, ``counters`` (one
  dict of per-step counts per call) and ``hlo_text``;
* ``finish()``: the outputs to the host and the device state freed;
* ``check()``: ``{name: (value, limit)}``, compared with its reference.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from benchmark import manifest, peaks as peaks_lib, xplane


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class RunRecord:
    """What the metric readers read."""

    cell: manifest.Cell
    seed: int
    chips: int
    device_kind: str
    setup_s: float
    call_s: List[float]
    window_s: float
    units_per_call: float
    steps_per_call: int
    shapes: dict
    counters: List[dict]  # per call of the window
    trace: Optional[xplane.Reduction] = None

    @property
    def calls(self) -> int:
        return len(self.call_s)

    @property
    def steps(self) -> int:
        return self.calls * self.steps_per_call

    @property
    def peaks(self) -> peaks_lib.ChipPeaks:
        return peaks_lib.peaks(self.device_kind)


class _Compiles:
    """Backend compiles and persistent-cache lookups, from jax.monitoring."""

    def __init__(self):
        self.compiles = 0
        self.requests = 0
        self.hits = 0

    def duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def configure_jax(jax) -> str:
    """The persistent compile cache at the fixed ``<checkout>/.jax_cache``
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), with every program
    written to it, however quickly it compiled."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(manifest.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def _span_factory(traced: bool) -> Callable:
    if not traced:
        return lambda _name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool, *,
        t0: float, require_chip: bool = True,
        patch: Optional[Callable] = None) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    devices = devices[: cell.chips]
    cache = configure_jax(jax)
    comp = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(comp.duration)
    jax.monitoring.register_event_listener(comp.event)

    t_devices = time.perf_counter() - t0
    work = cell.driver.build(cell.config, cell.traffic, seed, devices)
    if patch is not None:
        patch(work)
    t_build = time.perf_counter() - t0
    nospan = _span_factory(False)
    for _ in range(int(cell.traffic["warmup_calls"])):
        work.call(nospan)
    setup_s = time.perf_counter() - t0
    warm_calls = len(work.counters)
    log(f"setup: {setup_s:.3f} s (devices found at {t_devices:.3f} s, "
        f"state and program built at {t_build:.3f} s); compile cache "
        f"{cache}: {comp.hits} hit(s) of {comp.requests} request(s), "
        f"{comp.compiles} backend compile(s)")

    compiles0 = comp.compiles
    call_s: List[float] = []
    reduction = None
    if trace:
        span = _span_factory(True)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tmp)
            try:
                for _ in range(int(cell.traffic["trace_calls"])):
                    with span(xplane.CALL_SPAN):
                        a = time.perf_counter()
                        work.call(span)
                        call_s.append(time.perf_counter() - a)
            finally:
                jax.profiler.stop_trace()
            loaded = xplane.load(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        reduction = xplane.reduce(loaded, xplane.hlo_table(work.hlo_text),
                                  xplane.hlo_module(work.hlo_text))
        window_s = reduction.window_s
    else:
        start = time.perf_counter()
        deadline = start + seconds
        while True:
            a = time.perf_counter()
            work.call(nospan)
            b = time.perf_counter()
            call_s.append(b - a)
            if b >= deadline:
                break
        window_s = b - start
    in_window = comp.compiles - compiles0
    backlog = sum(int(c.get("backlog", 0).sum())
                  for c in work.counters[warm_calls:])
    ms = sorted(1e3 * c for c in call_s)
    log(f"window: {len(call_s)} call(s) of {work.steps_per_call} step(s) "
        f"in {window_s:.3f} s (call ms min {ms[0]:.3f}, median "
        f"{ms[len(ms) // 2]:.3f}, max {ms[-1]:.3f}); {in_window} "
        f"compile(s) inside the window; {backlog} migrant(s) held back by "
        f"flow control")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    work.finish()
    checks = work.check()
    correct = all(v <= lim for v, lim in checks.values())

    record = RunRecord(
        cell=cell, seed=seed, chips=cell.chips,
        device_kind=devices[0].device_kind, setup_s=setup_s, call_s=call_s,
        window_s=window_s, units_per_call=work.units_per_call,
        steps_per_call=work.steps_per_call, shapes=work.shapes,
        counters=work.counters[warm_calls:], trace=reduction,
    )
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.readers[m["name"]].read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": correct,
        "attempted": len(call_s),
        "failed": 0 if correct else len(call_s),
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    p = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip."
    )
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = manifest.resolve(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), t0=t0)
    except NoChip as e:
        log(f"benchmark: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
