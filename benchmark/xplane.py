"""Reading a profiler trace: device ops to layers, busy time, idle gaps.

Three steps, each checked on a recorded trace kept in ``tests/fixtures``:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
   a small JSON-ready dict: every op event on each device's ``XLA Ops``
   line, and the host spans the benchmark put around its own calls
   (names starting with ``bench:``).
2. :func:`hlo_table` maps each instruction of the compiled program's
   text to its ``op_name`` scope path and, for a Pallas kernel (a
   ``tpu_custom_call``), to the kernel's name.
3. :func:`reduce` joins the two: each device event's scope and kernel,
   the union of busy intervals in the traced window, and the idle gaps
   labelled by the host span that was open at the time. Only leaf
   events count as work: an event that holds others (a ``while`` loop
   holding its body, a ``conditional`` its branch, a ``call``) spans the
   gaps between them too, so its own time is idle, not busy.

Per-layer metrics (``metrics/*.py``) read a :class:`Reduction` with their
own predicate over scopes and kernels.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"
CALL_SPAN = "bench:call"


def trace_files(trace_dir: str) -> List[str]:
    return glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)


_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")


def instr_name(event_name: str) -> str:
    """The HLO instruction an op event names. A TPU trace names an op by
    its whole instruction line (``%fusion.8 = s32[...] fusion(...)``);
    the instruction is what stands before the ``=``."""
    m = _EVENT_INSTR.match(event_name)
    return m.group(1) if m else event_name


def load(trace_dir: str) -> dict:
    """The device op events and the benchmark's host spans of the one
    ``.xplane.pb`` under ``trace_dir``. Only the ``XLA Ops`` line counts:
    the ``Async XLA Ops`` line holds copies and slices that the DMA
    engines run beside the core's ops."""
    from jax.profiler import ProfileData

    paths = trace_files(trace_dir)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    events.append([
                        instr_name(ev.name), int(ev.start_ns),
                        int(ev.duration_ns), stats.get("hlo_module"),
                    ])
            devices.append({"name": plane.name, "events": events})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        )
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "host_spans": spans}


@dataclass(frozen=True)
class Instr:
    scope: tuple  # op_name path components
    kernel: Optional[str]  # Pallas kernel name, for a tpu_custom_call


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def hlo_table(hlo_text: str) -> Dict[str, Instr]:
    """Instruction name -> scope path and kernel, from compiled HLO text."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        scope = tuple(op.group(1).split("/")) if op else ()
        kernel = None
        if 'custom_call_target="tpu_custom_call"' in rest:
            kernel = re.sub(r"\.\d+$", "", name)
        table[name] = Instr(scope, kernel)
    return table


def hlo_module(hlo_text: str) -> Optional[str]:
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else None


@dataclass
class Op:
    name: str
    start: int
    dur: int
    scope: tuple = ()
    kernel: Optional[str] = None
    leaf: bool = True  # holds no other event

    @property
    def self_ns(self) -> int:
        """Time of work: the whole event for a leaf, none for an event
        that holds others (its own time is the gaps between them)."""
        return self.dur if self.leaf else 0

    def in_scope(self, *names: str) -> bool:
        return any(n in self.scope for n in names)

    @property
    def tag(self) -> str:
        """The kernel, else the innermost named scope (``a:b``), else -."""
        if self.kernel:
            return self.kernel
        for part in reversed(self.scope):
            if ":" in part:
                return part
        return "-"


@dataclass
class Reduction:
    window: tuple  # (start_ns, end_ns)
    devices: List[List[Op]]
    busy_ns: List[int]
    gaps: List[List[tuple]]  # per device: (start_ns, end_ns)
    host_spans: List[list] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran on a device, mean over devices."""
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9

    def time_s(self, pred: Callable[[Op], bool]) -> float:
        """Seconds of work of the ops ``pred`` selects (leaves only),
        mean over devices."""
        tot = sum(op.self_ns for ops in self.devices for op in ops if pred(op))
        return tot / len(self.devices) / 1e9

    def count(self, pred: Callable[[Op], bool]) -> float:
        """Events ``pred`` selects, mean over devices."""
        n = sum(1 for ops in self.devices for op in ops if pred(op))
        return n / len(self.devices)

    def host_label(self, t_ns: int) -> str:
        """The innermost benchmark span open at ``t_ns``."""
        best = None
        for name, start, dur in self.host_spans:
            if start <= t_ns < start + dur:
                if best is None or dur < best[1]:
                    best = (name, dur)
        return best[0] if best else "outside bench spans"

    def breakdown(self, top: int = 10) -> dict:
        """Device ops that took most time, and the longest idle gaps with
        what the host was doing; seconds are means over devices."""
        agg: Dict[str, float] = {}
        for ops in self.devices:
            for op in ops:
                if not op.leaf:
                    continue
                key = f"{op.tag} {op.name}"
                agg[key] = agg.get(key, 0.0) + op.self_ns / 1e9
        n = len(self.devices)
        ops = sorted(((k, v / n) for k, v in agg.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(
            ((self.host_label((a + b) // 2), (b - a) / 1e9)
             for dev in self.gaps for a, b in dev),
            key=lambda g: -g[1],
        )[:top]
        return {"device_ops": [list(o) for o in ops],
                "idle_gaps": [list(g) for g in gaps]}


def _mark_containers(events: List[Op]) -> None:
    """Mark every event that holds another as no leaf (events are sorted
    by start, outer first)."""
    stack: List[Op] = []
    for ev in events:
        while stack and stack[-1].start + stack[-1].dur <= ev.start:
            stack.pop()
        if stack:
            stack[-1].leaf = False
        stack.append(ev)


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def reduce(trace: dict, table: Dict[str, Instr],
           module: Optional[str] = None) -> Reduction:
    """Join a loaded trace with the program's instruction table.

    The window runs from the start of the first ``bench:call`` span to
    the end of the last; without host spans, from the first device op
    to the end of the last. Events outside it are dropped and those
    that straddle it clipped. Only events of ``module`` (when the trace
    names modules) are given scopes and kernels.
    """
    calls = [s for s in trace["host_spans"] if s[0] == CALL_SPAN]
    if calls:
        window = (calls[0][1], max(s[1] + s[2] for s in calls))
    else:
        evs = [e for d in trace["devices"] for e in d["events"]]
        if not evs:
            raise ValueError("the trace holds no device op")
        window = (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))
    w0, w1 = window
    devices, busy, gaps = [], [], []
    for dev in trace["devices"]:
        ops = []
        for name, start, dur, mod in sorted(dev["events"],
                                            key=lambda e: (e[1], -e[2])):
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            ins = None
            if module is None or mod is None or str(mod).startswith(module):
                ins = table.get(name)
            ops.append(Op(name, a, b - a,
                          ins.scope if ins else (),
                          ins.kernel if ins else None))
        _mark_containers(ops)
        spans = _union([(op.start, op.start + op.dur)
                        for op in ops if op.leaf])
        busy.append(sum(b - a for a, b in spans))
        holes, t = [], w0
        for a, b in spans:
            if a > t:
                holes.append((t, a))
            t = max(t, b)
        if t < w1:
            holes.append((t, w1))
        gaps.append(holes)
        devices.append(ops)
    if not devices:
        raise ValueError("the trace holds no device plane")
    return Reduction(window, devices, busy, gaps, list(trace["host_spans"]))
