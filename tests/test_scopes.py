"""Every costly device op of the drift loop and the service chunks sits
under a program scope.

The benchmark puts a step's device time down to layers by the
``op_name`` scope (``telemetry.phases.traced_span``) that the compiled
HLO carries on each instruction (``benchmark/xplane.py``). An op under
no scope is time no layer owns. These tests compile the programs on the
CPU mesh and read the same HLO text:

* every costly instruction (a fusion, sort, scatter, gather, all-to-all,
  dynamic-update-slice or custom-call) in the computations the program
  executes carries a ``mig:``, ``rd:``, ``svc:`` or ``pipe:`` scope;
* each scope of the drift loop holds its landmark op, on one device
  (2x2x2 vranks) and across four (2x2x1, one rank a device);
* the one-call planar vrank program that ``GridRedistribute.redistribute``
  dispatches on one device holds its boundary work under ``rd:fuse`` and
  ``rd:unfuse`` and every other costly op under the engine's ``rd:``
  scopes, and its plan copies windows, not pool-long gathers.

Two kinds of instruction are the compiler's, not the program's, and no
scope can reach them: the loop's own bookkeeping (the trip counter and
its compare, the per-step writes of the stacked outputs, the copies of
the carry made at the body's call) and a fusion of one operation, which
counts as that operation (the CPU backend wraps single ops this way).
"""

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_grid_redistribute_tpu import api
from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.models import nbody
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
from mpi_grid_redistribute_tpu.service import pipeline, resident

COSTLY = {"fusion", "sort", "scatter", "gather", "all-to-all",
          "dynamic-update-slice", "custom-call"}
# ops that compute nothing of their own inside a fusion
NOT_WORK = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "copy"}
PROGRAM_SCOPES = ("mig:", "rd:", "svc:", "pipe:")
# the loop's bookkeeping: its condition, ops directly in its body (the
# trip counter, the stacked outputs' per-step writes) and the body call
LOOP_OWN = re.compile(r"(^|/)while/(cond(/.*)?|body/[^/]+)$")

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"to_apply=%?([\w.\-]+)")
_FUSED = re.compile(r"calls=%?([\w.\-]+)")


def _opcode(rest):
    """The opcode of an instruction line's right-hand side (after its
    shape, which may be a tuple)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.index(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[i:])
    return m.group(1) if m else None


def _computations(text):
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            cur = h.group(2)
            comps[cur] = []
            if h.group(1):
                entry = cur
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            comps[cur].append((name, _opcode(rest), rest))
    return comps, entry


def executed_instructions(text):
    """``(name, opcode, op_name, fused_ops)`` of every instruction in the
    computations the program runs as its own events: the entry, loop
    bodies and conditions, conditional branches and calls (not fused
    computations or comparators). A fusion of one operation reports
    that operation's opcode; ``fused_ops`` is the set of opcodes a
    fusion holds (empty for other instructions)."""
    comps, entry = _computations(text)
    seen, todo, out = set(), [entry], []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for name, op, rest in comps[c]:
            todo += _CALLED.findall(rest)
            for b in _BRANCHES.findall(rest):
                todo += [x.strip().lstrip("%") for x in b.split(",")]
            if op == "call":
                todo += _TO_APPLY.findall(rest)
            fused_ops = set()
            if op == "fusion":
                fused = _FUSED.search(rest).group(1)
                fused_ops = {o for _, o, _ in comps.get(fused, [])}
                work = fused_ops - NOT_WORK
                if len(work) == 1:
                    op = next(iter(work))
            m = _OP_NAME.search(rest)
            out.append((name, op, m.group(1) if m else "", fused_ops))
    return out


def _branch_indices(text):
    """Instructions that compute a conditional's branch index: the
    convert ``lax.cond`` makes of its predicate, at the call's site."""
    comps, _ = _computations(text)
    return {
        re.match(r".*?conditional\(%?([\w.\-]+)", rest).group(1)
        for body in comps.values()
        for _, op, rest in body
        if op == "conditional"
    }


def _scoped(op_name):
    return any(p.startswith(PROGRAM_SCOPES) for p in op_name.split("/"))


def bare_costly(text):
    control = _branch_indices(text)
    return [
        (name, op, op_name)
        for name, op, op_name, _ in executed_instructions(text)
        if op in COSTLY and not _scoped(op_name)
        and not LOOP_OWN.search(op_name) and name not in control
    ]


# ------------------------------------------------------------ programs


def _drift_loop(dev_shape, grid_shape, n_local=512, capacity=None):
    """The benchmark's drift loop (``drivers/drift_loop.py``) at a tiny
    size: 90% fill, ~2% of the live rows crossing a face a step.
    ``capacity`` defaults to a quarter of the slots."""
    vshape = tuple(g // d for g, d in zip(grid_shape, dev_shape))
    n_dev = math.prod(dev_shape)
    dgrid = ProcessGrid(dev_shape)
    mesh = mesh_lib.make_mesh(dgrid, devices=jax.devices()[:n_dev])
    R = math.prod(grid_shape)
    cfg = nbody.DriftConfig(
        domain=Domain(0.0, 1.0, periodic=True), grid=dgrid, dt=1.0,
        capacity=capacity or n_local // 4, n_local=n_local,
        local_budget=n_local // 2, engine="auto",
    )
    vgrid = ProcessGrid(grid_shape) if math.prod(vshape) > 1 else None
    loop = nbody.make_migrate_loop(cfg, mesh, 8, vgrid=vgrid)
    sh = NamedSharding(mesh, P(dgrid.axis_names))
    N = R * n_local
    args = (
        jax.ShapeDtypeStruct((3 * N,), jnp.float32, sharding=sh),
        jax.ShapeDtypeStruct((3 * N,), jnp.float32, sharding=sh),
        jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=sh),
    )
    return jax.jit(loop).lower(*args).compile().as_text()


def _service_chunk(builder):
    """A service macro-step of 16 steps on 16 vranks (the pipelined one
    arms; the sequential one unrolls 8 steps a loop iteration)."""
    grid = (2, 2, 4)
    rd = api.GridRedistribute(
        grid=ProcessGrid(grid), lo=(0.0,) * 3, hi=(1.0,) * 3,
        periodic=(True,) * 3, engine="auto",
    )
    R, n_local = rd.nranks, 64
    rng = np.random.default_rng(11)
    pos = np.empty((R * n_local, 3), np.float32)
    for coords in np.ndindex(*grid):
        r = rd.grid.rank_of_cell(coords)
        pos[r * n_local:(r + 1) * n_local] = (
            np.asarray(coords, np.float32)
            + rng.random((n_local, 3), dtype=np.float32)
        ) / np.asarray(grid, np.float32)
    vel = ((rng.random((R * n_local, 3), dtype=np.float32) - 0.5) * 0.2)
    pos, vel = jnp.asarray(pos), jnp.asarray(vel)
    ids = jnp.arange(R * n_local, dtype=jnp.int32)
    count = jnp.full((R,), 3 * n_local // 4, jnp.int32)
    macro, _, _ = builder(rd, 0.05, 16, pos, vel, ids)
    return macro.lower(pos, vel, ids, count).compile().as_text()


def _redistribute_8v(n_local=256):
    """The one-call planar vrank program for a row of position, velocity
    and a 64-bit id (3 + 3 + 2 words; the id arrives as its two int32
    words), 2x2x2 ranks on one device."""
    R = 8
    rows = jax.ShapeDtypeStruct((R * n_local, 3), jnp.float32)
    words = jax.ShapeDtypeStruct((R * n_local, 2), jnp.int32)
    count = jax.ShapeDtypeStruct((R,), jnp.int32)
    specs = api._planar_specs(rows, (rows, words))
    fn = api._build_planar_vranks_call(
        Domain(0.0, 1.0, periodic=True), ProcessGrid((2, 2, 2)),
        n_local // 2, 2 * n_local, specs,
    )
    return fn.lower(rows, count, rows, words).compile().as_text()


PROGRAMS = {
    "drift_loop_8v_1dev": lambda: _drift_loop((1, 1, 1), (2, 2, 2)),
    "redistribute_planar_8v": _redistribute_8v,
    # a write plan of 4 * 256 = 1024 entries against 2048 slots a device,
    # so an op as long as the slots is no plan-sized op (as on the chip)
    "drift_loop_4dev": lambda: _drift_loop((2, 2, 1), (2, 2, 1), 2048, 256),
    "resident_chunk": lambda: _service_chunk(resident.make_chunk_fn),
    "pipelined_chunk": lambda: _service_chunk(
        pipeline.make_pipelined_chunk_fn
    ),
}
_TEXT = {}


def hlo(name):
    if name not in _TEXT:
        _TEXT[name] = PROGRAMS[name]()
    return _TEXT[name]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_costly_op_has_a_program_scope(program):
    text = hlo(program)
    assert any(i[1] in COSTLY for i in executed_instructions(text))
    assert bare_costly(text) == []


def _ops_under(text, scope):
    return [i for i in executed_instructions(text)
            if scope in i[2].split("/")]


def _in_loop(op_name):
    return "/while/body/" in op_name


# scope -> layout -> predicate on (opcode, op_name, fused_ops) that
# finds the scope's landmark op
LANDMARKS = {
    # the per-call free-stack argsort, before the loop
    "mig:enter": lambda op, n, f: op == "sort" and "argsort" in n
    and not _in_loop(n),
    # the planar split of the fused state after the loop
    "mig:exit": lambda op, n, f: "bitcast-convert" in f
    and not _in_loop(n),
    # the drift, p + v * dt, in every step
    "mig:drift": lambda op, n, f: "add" in f and _in_loop(n),
    # the grant fixpoint's greedy allocation, in every step
    "mig:grant": lambda op, n, f: "_greedy_alloc" in n and _in_loop(n),
    # _stack_push_pop's window write: vmapped on one device (a scatter
    # on the CPU), the flat engine's own on four
    "mig:stack": lambda op, n, f: _in_loop(n)
    and bool(({op} | f) & {"scatter", "dynamic-update-slice"}),
}
LAYOUTS = ("drift_loop_8v_1dev", "drift_loop_4dev")
METRIC_SCOPES = {"mig:select", "mig:pack", "mig:exchange", "mig:unpack",
                 "mig:bin"}


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("scope", sorted(LANDMARKS))
def test_each_drift_loop_scope_holds_its_landmark(scope, layout):
    text = hlo(layout)
    under = _ops_under(text, scope)
    assert any(LANDMARKS[scope](op, n, f) for _, op, n, f in under), under
    if scope == "mig:grant" and layout == "drift_loop_4dev":
        # the desired counts out, the grants back
        assert sum(i[1] == "all-to-all" for i in under) == 2
    # a new scope holds no op of the scopes the metrics already read
    for _, _, n, _ in under:
        assert not METRIC_SCOPES & set(n.split("/")), n


_SHAPE = re.compile(r"^[a-z]\w*\[([\d,]*)\]")


def _gather_lengths(text, scopes):
    """``(name, op_name, dims)`` of every gather the program runs under
    one of ``scopes``, standalone or inside a fusion, with the dims of
    the gather's own output."""
    comps, _ = _computations(text)
    line = {nm: rest for body in comps.values() for nm, _, rest in body}
    out = []
    for name, op, op_name, _ in executed_instructions(text):
        if not set(scopes) & set(op_name.split("/")):
            continue
        fused = _FUSED.search(line[name])
        if fused:
            rests = [r for _, o, r in comps.get(fused.group(1), [])
                     if o == "gather"]
        else:
            rests = [line[name]] if op == "gather" else []
        for r in rests:
            dims = _SHAPE.match(r).group(1).split(",")
            out.append((name, op_name, tuple(int(d) for d in dims if d)))
    return out


def test_four_device_landing_has_no_slot_long_gather():
    """The flat engine's landing and stack update gather over the write
    plan, never over every slot: no gather under ``mig:unpack`` or
    ``mig:stack`` has an output as long as the device's 2048 slots."""
    text = hlo("drift_loop_4dev")
    gathers = _gather_lengths(text, ("mig:unpack", "mig:stack"))
    assert gathers, "the landing's plan gathers are missing"
    assert [g for g in gathers if 2048 in g[2]] == []


# scope -> predicate on (opcode, op_name, fused_ops) that finds the
# one-call program's landmark op under it
RD_LANDMARKS = {
    # the caller's row-major arrays into [V, K, n] planar rows: the
    # transposes, which carry the scope inside the fusions that read them
    "rd:fuse": lambda op, n, f: "transpose" in f,
    # the destination sort of every rank's rows, the rows carried
    "rd:bin": lambda op, n, f: op == "sort",
    # the window copies into the send pool: one gather of whole windows
    # out of the sorted rows, padded so that no window start is clamped
    "rd:pack": lambda op, n, f: {"gather", "pad"} <= f,
    # the window copies into receive order, one in-place update each
    "rd:unpack": lambda op, n, f: "dynamic-update-slice" in ({op} | f),
    # the fused rows back to row-major outputs, positions as float32
    "rd:unfuse": lambda op, n, f: "bitcast-convert" in f,
}


def _fused_into(text, scope):
    """``(name, opcode, op_name, scope_ops)`` of executed fusions rooted
    under another scope that hold instructions of ``scope`` inside;
    ``scope_ops`` are those instructions' opcodes alone, so a landmark
    found there is ``scope``'s own op, not its host fusion's."""
    comps, _ = _computations(text)
    line = {nm: rest for body in comps.values() for nm, _, rest in body}
    out = []
    for name, op, op_name, fused_ops in executed_instructions(text):
        fused = _FUSED.search(line[name]) if fused_ops else None
        if fused is None or scope in op_name.split("/"):
            continue
        own = {
            o for _, o, r in comps.get(fused.group(1), [])
            if scope in (_OP_NAME.search(r) or [None, ""])[1].split("/")
        }
        if own:
            out.append((name, op, op_name, own))
    return out


@pytest.mark.parametrize("scope", sorted(RD_LANDMARKS))
def test_each_redistribute_scope_holds_its_landmark(scope):
    text = hlo("redistribute_planar_8v")
    under = _ops_under(text, scope)
    if scope == "rd:fuse":
        # the fused state is read only row by row (the destination sort's
        # operands), so the CPU compiler folds the fuse's transposes into
        # those reads, under rd:bin roots; the TPU keeps fusions rooted
        # under rd:fuse (test_tpu_compile pins them). Here the transposes
        # themselves must carry the scope.
        under += _fused_into(text, scope)
    assert any(RD_LANDMARKS[scope](op, n, f) for _, op, n, f in under), under
    # every scope wraps its phase whole: no op carries it inside a vmap
    assert f"vmap({scope})" not in text


def test_one_call_landing_has_no_sort_or_scatter():
    """At the default capacity the one-call landing copies windows into
    place: no sort and no scatter runs under ``rd:unpack``, standalone or
    inside a fusion."""
    text = hlo("redistribute_planar_8v")
    under = _ops_under(text, "rd:unpack")
    assert under
    assert [i for i in under if {"sort", "scatter"} & ({i[1]} | i[3])] == []


def test_one_call_plan_has_no_pool_long_gather():
    """The one-call plan copies each destination's slots as one window of
    the sorted rows, never one index a pool column: no gather under
    ``rd:bin`` or ``rd:pack`` has an output dimension as long as one
    vrank's send pool (8 destinations x capacity 128 = 1024 columns)."""
    text = hlo("redistribute_planar_8v")
    gathers = _gather_lengths(text, ("rd:bin", "rd:pack"))
    assert any(g[2][-1] == 128 for g in gathers), gathers
    assert [g for g in gathers if max(g[2]) >= 1024] == []
