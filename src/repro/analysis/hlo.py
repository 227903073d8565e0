"""Computation-graph helpers over post-optimization HLO text.

The rule registry (:mod:`repro.analysis.rules`) reasons about *structure* —
which computations a program can reach unconditionally, which only through a
conditional branch, and which input buffers the module aliases to outputs.
This module owns that parsing; per-instruction cost accounting stays in
:mod:`repro.launch.hlo_cost`.

Everything here is pure text analysis: no jax import, no device state —
rules can run in any process on HLO captured elsewhere.
"""

from __future__ import annotations

import re

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
# Edges that always execute when the caller executes (while bodies and
# conditions run on every iteration; calls/fusions run inline) ...
_UNCOND_CALL_RE = re.compile(
    r"(?:calls=|body=|condition=|to_apply=)%?([\w.\-]+)"
)
# ... vs. edges that execute only when their branch is selected.
_BRANCH_RE = re.compile(
    r"(?:branch_computations=\{([^}]*)\}"
    r"|true_computation=%?([\w.\-]+)"
    r"|false_computation=%?([\w.\-]+))"
)


def parse_computations(hlo: str) -> tuple[dict[str, list[str]], str | None]:
    """``(computations, entry_name)``: each computation's instruction lines
    (stripped), plus the name of the ENTRY computation (``None`` when the
    text has no ENTRY marker)."""
    comps: dict[str, list[str]] = {}
    entry: str | None = None
    current: str | None = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        m = _COMP_RE.match(line.strip())
        if m and line.endswith("{"):
            current = m.group(1)
            comps[current] = []
            if line.lstrip().startswith("ENTRY"):
                entry = current
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(line.strip())
    return comps, entry


_DEF_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+\[[^\]]*\])")


def instruction_shapes(comps: dict[str, list[str]]) -> dict[str, str]:
    """``{instruction name: array type}`` (e.g. ``'f32[6,6]'``) over every
    computation.  Instruction names are unique within a module, and the
    printed HLO names an operand without its type, so a rule that asks
    what an operand is looks its name up here."""
    shapes: dict[str, str] = {}
    for body in comps.values():
        for line in body:
            m = _DEF_RE.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)
    return shapes


def operand_names(line: str, op: str) -> list[str]:
    """Operand names of the ``op(...)`` call on one instruction line."""
    m = re.search(rf"\b{re.escape(op)}\(([^)]*)\)", line)
    if not m:
        return []
    return [tok.split()[-1].lstrip("%") for tok in m.group(1).split(",")
            if tok.strip()]


def conditional_branches(line: str) -> list[str]:
    """Branch computation names of one ``conditional(...)`` instruction."""
    branches: list[str] = []
    for m in _BRANCH_RE.finditer(line):
        if m.group(1):
            branches += [b.strip().lstrip("%") for b in m.group(1).split(",")]
        else:
            branches.append((m.group(2) or m.group(3)).strip())
    return branches


def reachable(
    comps: dict[str, list[str]],
    root: str,
    *,
    include_branches: bool = True,
) -> set[str]:
    """Computations reachable from ``root`` through call edges.

    ``include_branches=False`` follows only the edges that execute whenever
    the caller executes (calls, fusions, while bodies/conditions) and stops
    at conditional branches — the result is the set of computations the
    program runs *unconditionally*, which is exactly what the
    ``conditional-comm`` rule needs to prove a combine is gated.
    """
    seen, frontier = {root}, [root]
    while frontier:
        c = frontier.pop()
        for ins in comps.get(c, []):
            callees = list(_UNCOND_CALL_RE.findall(ins))
            if include_branches:
                callees += conditional_branches(ins)
            for callee in callees:
                if callee in comps and callee not in seen:
                    seen.add(callee)
                    frontier.append(callee)
    return seen


def conditional_lines(comps: dict[str, list[str]]) -> list[str]:
    """Every ``conditional(...)`` instruction in the module."""
    return [
        line
        for body in comps.values()
        for line in body
        if re.search(r"\bconditional\(", line)
    ]


def alias_entries(hlo: str) -> int:
    """Number of ``input_output_alias`` entries the module header declares.

    XLA records one entry per donated buffer it could actually alias to an
    output; a donated buffer that forced a defensive copy simply has no
    entry — so this count against the donated-leaf count is the
    donation-honored check.
    """
    start = hlo.find("input_output_alias={")
    if start < 0:
        return 0
    i = hlo.index("{", start)
    depth, j = 0, i
    while j < len(hlo):
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    body = hlo[i : j + 1]
    return len(re.findall(r":\s*\(", body))
