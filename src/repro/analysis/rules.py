"""Declarative rule registry over lowered jaxprs and compiled HLO.

Every compiled-program invariant in the repo lives here, exactly once:

  collective-budget   the agent combine moves deg·shard permute bytes (not
                      K·shard) and the config stays under its pinned
                      per-device collective ceiling
  wire-dtype-leak     a bf16 combine ships u16 on the wire; full-width
                      permute traffic standing in for it is the bug class
                      the u16 bitcast exists to prevent
  conditional-comm    with combine_every > 1, the K×K mixing dot and the
                      combine's permutes are reachable only through a
                      conditional branch — skipped steps pay zero comm
  donation-honored    buffers donated to jit show up as input_output_alias
                      entries; a missing entry is a defensive copy
  retrace-guard       traced steps carry no weak-type python scalars or
                      host callbacks, and jit caches report exactly the
                      expected number of compilations

Rules consume a :class:`LintContext` and return :class:`Finding`s.  The
module imports no jax — jaxprs arrive as objects and are only attribute-
inspected, HLO arrives as text — so rules run in any process on programs
captured elsewhere.  Drivers that *build* contexts live in
:mod:`repro.analysis.run`.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections.abc import Callable, Iterator
from typing import Any

from repro.analysis import hlo as H
from repro.launch.hlo_cost import HloCost


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation. ``detail`` carries the numbers for the JSON
    report; ``message`` is the human line."""

    rule: str
    message: str
    detail: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        return {"rule": self.rule, "message": self.message,
                "detail": self.detail}


@dataclasses.dataclass
class LintContext:
    """Everything a rule may look at for one lowered program.

    Populate only what you have: each rule declares which fields make it
    applicable and is skipped (recorded in ``LintReport.skipped``) when
    they are missing.  ``records`` is scratch output — rules stash their
    measured numbers there even when clean, so drivers can report
    measurements, not just violations.
    """

    hlo: str | None = None
    jaxpr: Any = None  # jax ClosedJaxpr (attribute-inspected only)
    n_dev: int = 1
    K: int = 1
    degree: int | None = None
    shard_bytes: int = 0
    wire_dtype: str | None = None
    emits_permutes: bool = True
    combine_every: int = 1
    slack: float = 0.25
    budget_ceiling: int | None = None
    expected_aliases: int | None = None
    min_alias_fraction: float = 0.9
    compile_counts: dict[str, dict] | None = None
    extra: dict = dataclasses.field(default_factory=dict)
    records: dict = dataclasses.field(default_factory=dict)
    _cost: HloCost | None = dataclasses.field(default=None, repr=False)

    def cost(self) -> HloCost:
        """Memoized HloCost over ``hlo`` (parsing big HLO once, not once
        per rule)."""
        if self._cost is None:
            if self.hlo is None:
                raise ValueError("LintContext has no HLO text")
            self._cost = HloCost(self.hlo, n_dev=self.n_dev)
        return self._cost


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    description: str
    applies: Callable[[LintContext], bool]
    check: Callable[[LintContext], list[Finding]]


RULES: dict[str, Rule] = {}


def register_rule(
    name: str, description: str, applies: Callable[[LintContext], bool]
) -> Callable[[Callable[[LintContext], list[Finding]]], Rule]:
    def deco(fn: Callable[[LintContext], list[Finding]]) -> Rule:
        rule = Rule(name, description, applies, fn)
        RULES[name] = rule
        return rule

    return deco


@dataclasses.dataclass
class LintReport:
    findings: list[Finding]
    checked: list[str]
    skipped: list[str]
    records: dict

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "findings": [f.to_json() for f in self.findings],
            "checked": self.checked,
            "skipped": self.skipped,
            "records": self.records,
        }


def run_rules(
    ctx: LintContext, only: list[str] | None = None
) -> LintReport:
    """Run every registered (or selected) rule whose preconditions the
    context satisfies."""
    findings: list[Finding] = []
    checked: list[str] = []
    skipped: list[str] = []
    names = list(RULES) if only is None else list(only)
    for name in names:
        rule = RULES[name]
        if not rule.applies(ctx):
            skipped.append(name)
            continue
        checked.append(name)
        findings.extend(rule.check(ctx))
    return LintReport(findings, checked, skipped, dict(ctx.records))


# ---------------------------------------------------------------------------
# collective-budget — deg·shard window + pinned ceiling
# ---------------------------------------------------------------------------


def combine_window(
    hlo: str | None = None,
    n_dev: int = 1,
    *,
    degree: int,
    shard_bytes: int,
    slack: float = 0.25,
    wire_dtype: str | None = None,
    cost: HloCost | None = None,
) -> dict:
    """Measure the agent combine's wire cost in post-SPMD HLO.

    The ppermute combine must move exactly ``degree`` rounds of one
    per-device parameter shard: total collective-permute wire bytes in
    ``[deg·shard, (1+slack)·deg·shard]``.  The lower bound catches a
    combine that silently stopped being lowered; the upper bound catches
    K-scaling regressions (dense all-gather re-emerging: K·shard ≫
    (1+slack)·deg·shard for any sparse graph) while absorbing small
    GSPMD resharding permutes.  ``shard_bytes`` must already be sized at
    the wire dtype (``tree_shard_bytes(..., elem_bytes=wire_elem_bytes)``)
    — a bf16 wire halves the whole window, so this check also catches a
    combine that silently fell back to the f32 wire.

    ``wire_dtype='bfloat16'``: the combine ships its payload bitcast to
    u16 (see core/diffusion.py's wire-format contract) and is the only
    u16 traffic in the program, so the window is applied to the u16
    permute bytes alone.  On meshes with a data axis this is what makes
    the check usable at all: activation-resharding permutes (bf16/f32)
    can dwarf the combine, but they can never masquerade as its wire.
    Other wire dtypes share their permute dtype with resharding traffic,
    so the window falls back to total permute bytes.

    Returns a record with ``ok`` plus the numbers; raises nothing —
    callers decide how loud to be.  This is the one implementation behind
    both the ``collective-budget`` rule and the legacy
    ``hlo_cost.agent_combine_check`` entry point.
    """
    if cost is None:
        if hlo is None:
            raise ValueError("combine_window needs hlo text or an HloCost")
        cost = HloCost(hlo, n_dev=n_dev)
    coll = cost.collectives()
    cp = coll["per_op"].get(
        "collective-permute",
        {"count": 0, "bytes": 0, "wire_bytes": 0, "by_dtype": {}},
    )
    if wire_dtype == "bfloat16":
        measured = cp.get("by_dtype", {}).get("u16", 0)
    else:
        measured = cp["wire_bytes"]
    expected = degree * shard_bytes
    ok = expected <= measured <= (1 + slack) * expected
    rec = {
        "degree": degree,
        "param_shard_bytes": shard_bytes,
        "expected_permute_bytes": expected,
        "permute_bytes": measured,
        "all_permute_bytes": cp["wire_bytes"],
        "permute_count": cp["count"],
        "total_collective_bytes": coll["total_bytes"],
        "ok": bool(ok),
    }
    if wire_dtype is not None:
        rec["wire_dtype"] = wire_dtype
    return rec


@register_rule(
    "collective-budget",
    "combine permute bytes sit in the deg·shard window and total "
    "collective bytes stay under the pinned per-config ceiling",
    lambda ctx: ctx.hlo is not None
    and ctx.degree is not None
    and (ctx.shard_bytes > 0 or ctx.budget_ceiling is not None),
)
def _collective_budget(ctx: LintContext) -> list[Finding]:
    rec = combine_window(
        cost=ctx.cost(),
        degree=ctx.degree or 0,
        shard_bytes=ctx.shard_bytes,
        slack=ctx.slack,
        wire_dtype=ctx.wire_dtype,
    )
    ctx.records["collective-budget"] = rec
    findings = []
    if not rec["ok"]:
        lo = rec["expected_permute_bytes"]
        hi = (1 + ctx.slack) * lo
        side = "below" if rec["permute_bytes"] < lo else "above"
        findings.append(
            Finding(
                "collective-budget",
                f"combine collective-permute bytes "
                f"{rec['permute_bytes']:.3e} {side} the deg·shard window "
                f"[{lo:.3e}, {hi:.3e}] (deg={rec['degree']}, "
                f"shard={rec['param_shard_bytes']:.3e} B) — the ring "
                f"combine must move deg per-agent shards, not K",
                dict(rec),
            )
        )
    if ctx.budget_ceiling is not None:
        total = rec["total_collective_bytes"]
        if total > ctx.budget_ceiling:
            findings.append(
                Finding(
                    "collective-budget",
                    f"total collective bytes {total:.3e} exceed the "
                    f"pinned ceiling {ctx.budget_ceiling:.3e} — TP/FSDP "
                    f"collectives regressed (or re-pin the budget if the "
                    f"change is intentional)",
                    {"total_collective_bytes": total,
                     "ceiling": ctx.budget_ceiling},
                )
            )
    return findings


# ---------------------------------------------------------------------------
# wire-dtype-leak — bf16 combine payload must travel as u16
# ---------------------------------------------------------------------------


@register_rule(
    "wire-dtype-leak",
    "a bf16 combine's permute traffic is u16-bitcast; full-width f32/bf16 "
    "permutes carrying the payload instead are a leak",
    lambda ctx: ctx.hlo is not None
    and ctx.wire_dtype == "bfloat16"
    and ctx.emits_permutes
    and (ctx.degree or 0) > 0
    and ctx.shard_bytes > 0,
)
def _wire_dtype_leak(ctx: LintContext) -> list[Finding]:
    cp = ctx.cost().collectives()["per_op"].get(
        "collective-permute",
        {"count": 0, "wire_bytes": 0, "by_dtype": {}},
    )
    by_dtype = dict(cp.get("by_dtype", {}))
    u16 = by_dtype.get("u16", 0)
    expected = (ctx.degree or 0) * ctx.shard_bytes
    ctx.records["wire-dtype-leak"] = {
        "u16_permute_bytes": u16,
        "expected_wire_bytes": expected,
        "permute_by_dtype": by_dtype,
    }
    if u16 >= expected:
        return []
    if u16 == 0:
        msg = (
            f"no u16 collective-permute traffic at all — the bf16 combine "
            f"payload is travelling at full width (permute bytes by "
            f"dtype: {by_dtype or 'none'})"
        )
    else:
        msg = (
            f"u16 collective-permute bytes {u16:.3e} below the combine's "
            f"wire size deg·shard = {expected:.3e} — part of the bf16 "
            f"payload leaked to a wider dtype (by dtype: {by_dtype})"
        )
    return [
        Finding(
            "wire-dtype-leak",
            msg,
            {"u16_permute_bytes": u16, "expected_wire_bytes": expected,
             "permute_by_dtype": by_dtype},
        )
    ]


# ---------------------------------------------------------------------------
# conditional-comm — combine_every > 1 gates all combine compute + comm
# ---------------------------------------------------------------------------


def _marker_lines(lines: list[str], K: int, wire_dtype: str | None,
                  shapes: dict[str, str]) -> list[str]:
    """Instructions that implement the combine: a dot with a K×K float
    operand (the mixing matrix, looked up in ``shapes`` by name), and (on a
    bf16 wire) u16 collective-permutes — nothing else in the program
    produces either."""
    mix = re.compile(rf"(?:f32|bf16|f64)\[{K},{K}\]$")
    out = []
    for line in lines:
        if " dot(" in line and any(
                mix.match(shapes.get(name, ""))
                for name in H.operand_names(line, "dot")):
            out.append(line)
        elif (
            wire_dtype == "bfloat16"
            and "collective-permute" in line
            and "u16[" in line
        ):
            out.append(line)
    return out


@register_rule(
    "conditional-comm",
    "with combine_every > 1 the K×K combine dot and the combine's "
    "permutes are reachable only through a conditional branch",
    lambda ctx: ctx.hlo is not None and ctx.combine_every > 1 and ctx.K > 1,
)
def _conditional_comm(ctx: LintContext) -> list[Finding]:
    comps, entry = H.parse_computations(ctx.hlo or "")
    if entry is None:
        entry = max(comps, key=lambda c: len(comps[c])) if comps else ""
    shapes = H.instruction_shapes(comps)
    marked = {
        name
        for name, lines in comps.items()
        if _marker_lines(lines, ctx.K, ctx.wire_dtype, shapes)
    }
    findings: list[Finding] = []
    if not marked:
        return [
            Finding(
                "conditional-comm",
                f"combine_every={ctx.combine_every} but no combine markers "
                f"(f32[{ctx.K},{ctx.K}] dot / wire permutes) anywhere in "
                f"the module — the combine was not lowered at all",
                {"K": ctx.K, "combine_every": ctx.combine_every},
            )
        ]
    uncond = H.reachable(comps, entry, include_branches=False)
    leaked = sorted(uncond & marked)
    if leaked:
        findings.append(
            Finding(
                "conditional-comm",
                f"combine instructions run unconditionally (reachable "
                f"from ENTRY without crossing a conditional branch) in "
                f"computations {leaked} — skipped steps would still pay "
                f"the combine",
                {"computations": leaked},
            )
        )
    gated = False
    for line in H.conditional_lines(comps):
        hot = [
            b
            for b in H.conditional_branches(line)
            if H.reachable(comps, b) & marked
        ]
        if len(hot) == 1:
            gated = True
        elif len(hot) > 1:
            findings.append(
                Finding(
                    "conditional-comm",
                    f"a conditional reaches combine instructions through "
                    f"{len(hot)} of its branches ({hot}) — both arms pay "
                    f"the combine, so the gate is vacuous",
                    {"branches": hot},
                )
            )
    if not gated and not leaked:
        findings.append(
            Finding(
                "conditional-comm",
                "combine instructions exist but no conditional gates "
                "them through exactly one branch",
                {"marked": sorted(marked)},
            )
        )
    return findings


# ---------------------------------------------------------------------------
# donation-honored — donated buffers must alias, not copy
# ---------------------------------------------------------------------------


@register_rule(
    "donation-honored",
    "buffers donated to jit appear as input_output_alias entries; a "
    "donated buffer without one forced a defensive copy",
    lambda ctx: ctx.hlo is not None and ctx.expected_aliases is not None,
)
def _donation_honored(ctx: LintContext) -> list[Finding]:
    n = H.alias_entries(ctx.hlo or "")
    expected = int(ctx.expected_aliases or 0)
    need = math.ceil(expected * ctx.min_alias_fraction)
    ctx.records["donation-honored"] = {
        "alias_entries": n,
        "donated_leaves": expected,
        "required": need,
    }
    if n >= need:
        return []
    return [
        Finding(
            "donation-honored",
            f"only {n} of {expected} donated buffers are aliased to "
            f"outputs (need ≥ {need}) — XLA inserted defensive copies "
            f"instead of reusing the donated memory",
            {"alias_entries": n, "donated_leaves": expected,
             "required": need},
        )
    ]


# ---------------------------------------------------------------------------
# retrace-guard — no weak-type scalars / host callbacks; jit caches stay 1
# ---------------------------------------------------------------------------

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback", "callback")


def _walk_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every eqn in a (Closed)Jaxpr, recursing into sub-jaxprs held in
    eqn params (cond branches, scan bodies, pjit calls, ...)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in getattr(inner, "eqns", []):
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _walk_eqns(sub)


@register_rule(
    "retrace-guard",
    "traced steps carry no weak-type python-scalar inputs or host "
    "callbacks, and jit caches report exactly the expected compiles",
    lambda ctx: ctx.jaxpr is not None or ctx.compile_counts is not None,
)
def _retrace_guard(ctx: LintContext) -> list[Finding]:
    findings: list[Finding] = []
    if ctx.jaxpr is not None:
        inner = getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr)
        weak = [
            str(v)
            for v in getattr(inner, "invars", [])
            if getattr(getattr(v, "aval", None), "weak_type", False)
        ]
        if weak:
            findings.append(
                Finding(
                    "retrace-guard",
                    f"traced step takes weak-typed inputs {weak} — a "
                    f"python scalar leaked into the trace, so every new "
                    f"value retriggers compilation; pass a jnp array or "
                    f"close over the constant",
                    {"weak_invars": weak},
                )
            )
        hostcalls = sorted(
            {
                eqn.primitive.name
                for eqn in _walk_eqns(ctx.jaxpr)
                if any(eqn.primitive.name.startswith(p)
                       for p in _CALLBACK_PRIMS)
            }
        )
        if hostcalls:
            findings.append(
                Finding(
                    "retrace-guard",
                    f"traced step contains host callbacks {hostcalls} — "
                    f"each dispatch round-trips to python, defeating the "
                    f"dispatch-free superstep driver",
                    {"callbacks": hostcalls},
                )
            )
    for name, counts in (ctx.compile_counts or {}).items():
        compiles = counts.get("compiles")
        expected = counts.get("expected", 1)
        if compiles is None:
            continue  # jax build without a readable cache size
        if compiles > expected:
            findings.append(
                Finding(
                    "retrace-guard",
                    f"{name} compiled {compiles}× across "
                    f"{counts.get('dispatches', '?')} dispatches "
                    f"(expected {expected}) — a shape/dtype/weak-type "
                    f"mismatch is forcing retraces",
                    dict(counts, fn=name),
                )
            )
    return findings


class CompileCounter:
    """Read a jitted function's compilation-cache size — the
    jit-cache-miss counter behind retrace-guard's compile assertions.

    ``count()`` returns None on jax builds without a readable cache size
    (callers must treat None as "unknown", not zero).
    """

    def __init__(self, jitted: Any):
        self._jitted = jitted

    def count(self) -> int | None:
        getter = getattr(self._jitted, "_cache_size", None)
        if getter is None:
            return None
        try:
            return int(getter())
        except Exception:
            return None
