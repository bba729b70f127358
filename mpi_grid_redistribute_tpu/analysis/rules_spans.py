"""G010 — marked hot paths must carry at least one named span.

Layer attribution reads XLA op metadata to map a profiler trace's
device time back to engine layers: ``jax.named_scope`` (wrapped as
``telemetry.phases.traced_span``) stamps every op traced inside it, so
a profiler session over a marked engine shows ``mig:pack`` /
``mig:unpack`` lanes instead of op soup, and the benchmark's trace
reduction (``benchmark/xplane.py``) puts each op's time down to the
layer its scope names. That coverage erodes silently — a refactor
that drops the span, or a new engine that never gained one, costs
nothing in any correctness suite; the next chip trace just comes back
unattributable.

This rule makes span coverage a lint invariant: every function marked
``# gridlint: fastpath-engine`` (G006's cost-contract marker) or
``# gridlint: resident-path`` (G009's sync-contract marker) must
lexically contain at least one ``jax.named_scope`` / ``named_scope`` /
``traced_span`` call — nested defs included, since scan bodies are
where the hot work lives. Host-side ``span()`` (a Perfetto
``TraceAnnotation``) does NOT satisfy the rule: it labels host wall
time, not traced ops, and the attribution gap G010 guards is on the
device timeline.

Like the other marker rules the check is lexical — a span inside a
helper CALLED from the marked function does not count, because the
marker names the function whose trace must be self-describing.
"""

from __future__ import annotations

import ast
import re
from typing import List

from mpi_grid_redistribute_tpu.analysis.core import (
    Finding,
    Project,
    call_name,
    last_attr,
    rule,
)

_MARKER_RE = re.compile(
    r"#\s*gridlint:\s*(?:fastpath-engine|resident-path)\b"
)
_SPAN_TAILS = ("named_scope", "traced_span")


def _is_marked(fi, mod) -> bool:
    node = fi.node
    if isinstance(node, ast.Lambda):
        return False
    first = min(
        [node.lineno] + [d.lineno for d in node.decorator_list]
    )
    if first < 2 or first - 2 >= len(mod.lines):
        return False
    return bool(_MARKER_RE.search(mod.lines[first - 2]))


def _has_span(fn_node) -> bool:
    for call in ast.walk(fn_node):
        if not isinstance(call, ast.Call):
            continue
        if last_attr(call_name(call)) in _SPAN_TAILS:
            return True
    return False


@rule("G010")
def check_spans(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules:
        for fi in mod.functions.values():
            if not _is_marked(fi, mod):
                continue
            if _has_span(fi.node):
                continue
            findings.append(
                Finding(
                    "G010",
                    mod.relpath,
                    fi.node.lineno,
                    fi.node.col_offset,
                    "marked hot path contains no named_scope span — "
                    "a profiler trace cannot put this function's "
                    "device time down to a layer; add a "
                    "telemetry.phases.traced_span around its hot region",
                    fi.qualname,
                )
            )
    return findings
