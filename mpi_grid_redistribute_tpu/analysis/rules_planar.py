"""G004 — planar-engine 32-bit word contract.

The planar halo/exchange engines move rows as fused 32-bit words:
``fuse_fields`` packs an (n, k) field block into one ``uint32`` word
stream via ``lax.bitcast_convert_type``, and the planar one-hot kernels
scatter those words as half-planes. The scheme is sound for 4-byte
element types, and for 8-byte ones that the fuse splits into two words
each (``api._fuse_planar``); a 2-byte row reads past its lane, and an
8-byte one bitcast as if it were 4 bytes truncates. ``api._planar_specs``
(through ``api._planar_refusal``) is the canonical guard: it refuses the
planar path unless every ``dtype.itemsize`` is 4 or 8 (positions 4).

G004 flags:

* call sites of ``fuse_fields`` / ``_fuse_planar`` with no ``.itemsize``
  comparison anywhere in (a) the called function's own body, (b) the
  call site's lexical scope chain, or (c) a same-module caller of the
  enclosing function (the guard is often one frame up, as with
  ``_planar_specs`` gating ``build_halo_planar``);
* ``lax.bitcast_convert_type`` applied directly to a parameter of a
  top-level function with no ``.itemsize`` check in the scope chain —
  i.e. a public entry point that bitcasts caller data unguarded.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from mpi_grid_redistribute_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    ModuleInfo,
    Project,
    call_name,
    last_attr,
    rule,
)

_FUSE_NAMES = ("fuse_fields", "_fuse_planar")


def _has_itemsize_check(node: Optional[ast.AST]) -> bool:
    """True if ``node`` contains a comparison mentioning ``.itemsize``."""
    if node is None:
        return False
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Compare):
            continue
        for part in ast.walk(sub):
            if isinstance(part, ast.Attribute) and part.attr == "itemsize":
                return True
    return False


def _scope_chain_checked(fi: Optional[FunctionInfo]) -> bool:
    while fi is not None:
        if _has_itemsize_check(fi.node):
            return True
        fi = fi.parent
    return False


def _guarded(project: Project, mod: ModuleInfo, fi: FunctionInfo) -> bool:
    """A function is guarded when its own body carries an itemsize
    check, or it calls a helper whose body does (``redistribute`` gates
    the planar path on ``_planar_specs(...) is not None`` — the compare
    lives one hop down, inside the helper)."""
    if _has_itemsize_check(fi.node):
        return True
    for n in ast.walk(fi.node):
        if not isinstance(n, ast.Call):
            continue
        nm = call_name(n)
        if not nm:
            continue
        for tgt in project.resolve_call_target(mod, nm, fi):
            if tgt is not fi and _has_itemsize_check(tgt.node):
                return True
    return False


def _top_ancestor(fi: FunctionInfo) -> FunctionInfo:
    while fi.parent is not None:
        fi = fi.parent
    return fi


def _same_module_caller_checked(
    project: Project, mod: ModuleInfo, fi: FunctionInfo
) -> bool:
    """True if some function in ``mod`` that calls ``fi``'s top-level
    ancestor (by simple name) is guarded — the one-frame-up shape where
    ``redistribute`` checks ``_planar_specs`` before invoking the
    planar builder whose nested ``call`` does the fusing."""
    target = _top_ancestor(fi).name
    for other in mod.functions.values():
        if other is fi or isinstance(other.node, ast.Lambda):
            continue
        calls_target = any(
            isinstance(n, ast.Call) and last_attr(call_name(n)) == target
            for n in ast.walk(other.node)
        )
        if calls_target and _guarded(project, mod, other):
            return True
    return False


def _enclosing(mod: ModuleInfo, node: ast.AST) -> Optional[FunctionInfo]:
    best: Optional[FunctionInfo] = None
    best_span: Optional[int] = None
    for fi in mod.functions.values():
        fn = fi.node
        lo, hi = fn.lineno, getattr(fn, "end_lineno", fn.lineno)
        if lo <= node.lineno <= hi:
            span = hi - lo
            if best_span is None or span < best_span:
                best, best_span = fi, span
    return best


@rule("G004")
def check_planar_contract(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            tail = last_attr(name)
            if tail in _FUSE_NAMES:
                enclosing = _enclosing(mod, node)
                if _scope_chain_checked(enclosing):
                    continue
                # does the fuse routine itself carry the guard?
                targets = project.resolve_call_target(mod, name, enclosing)
                if any(_has_itemsize_check(t.node) for t in targets):
                    continue
                if enclosing is not None and _same_module_caller_checked(
                    project, mod, enclosing
                ):
                    continue
                findings.append(
                    Finding(
                        "G004",
                        mod.relpath,
                        node.lineno,
                        node.col_offset,
                        f"{tail}(...) packs rows as 32-bit words but no "
                        f".itemsize check guards this call path; gate it "
                        f"like api._planar_specs (refuse when "
                        f"dtype.itemsize not in (4, 8), an 8-byte value "
                        f"split into two words)",
                        enclosing.qualname if enclosing else "<module>",
                    )
                )
            elif tail == "bitcast_convert_type":
                if not node.args or not isinstance(node.args[0], ast.Name):
                    continue
                enclosing = _enclosing(mod, node)
                if enclosing is None or enclosing.parent is not None:
                    # nested engine fns get their operands from an
                    # already-guarded builder; only top-level entry
                    # points bitcasting caller data count
                    continue
                if node.args[0].id not in enclosing.params:
                    continue
                if _scope_chain_checked(enclosing):
                    continue
                if _same_module_caller_checked(project, mod, enclosing):
                    continue
                findings.append(
                    Finding(
                        "G004",
                        mod.relpath,
                        node.lineno,
                        node.col_offset,
                        f"bitcast_convert_type on parameter "
                        f"'{node.args[0].id}' of a public entry point "
                        f"with no .itemsize guard; a non-4-byte dtype "
                        f"silently corrupts the fused word stream",
                        enclosing.qualname,
                    )
                )
    return findings
