"""Device time of the meta step split by the program's own named scopes.

The program names where its work happens with ``jax.named_scope``, in two
families: the meta-step's phases, ``dif.step.inner_adapt``,
``dif.step.outer_grad``, ``dif.step.hvp``, ``dif.step.outer_update`` and
``dif.step.combine``; and the model's blocks, ``dif.model.mixer``,
``dif.model.ffn`` and ``dif.model.head``.  A scope nested in another of
its family takes its ops: ``dif.model.moe_route`` or
``dif.model.moe_experts`` inside ``dif.model.ffn`` counts under
``moe_route`` or ``moe_experts`` and out of ``ffn``, so name a router's or
an expert layer's scope only where ``ffn_ms`` should lose its ops to
their own readers.  Its input pipeline marks each
meta-batch its producer thread makes with the host span
``dif.pipeline.produce``.

A scope reaches the compiled module's op metadata
(``metadata={op_name="jit(train_step)/vmap(vmap(dif.step.hvp))/..."}``)
but not the trace: a device op there is named by its HLO instruction
(``bench/trace.py``).  So an op is mapped to its scopes through the
compiled step's HLO text:

* :func:`op_scopes`: each instruction's innermost scope of each family on
  its ``op_name`` path;
* :func:`scope_times`: each op's self time in the window (as
  ``bench/trace.py`` takes it for its top ops) summed by the pair of its
  step and model scopes, averaged over the devices.  An op with no scope of
  a family counts as ``unscoped`` there, so each family's times add up to
  the device's busy time;
* :func:`program_spans`: the host events named ``dif.*``, on the trace's
  clock;
* :func:`per_step`: what a ``--trace 1`` run of the harness puts on its
  ``Run`` for the metric readers (``bench/metrics/<scope>_ms.py``, each
  through :func:`ms_under`), and its notes.

Run as a script, it runs one cell as ``bench/run.py --trace 1`` does, whose
result line it prints first, and then one more line: the traced steps'
device time by phase, by block and by the pair of them, in ms a step, with
the coverage of the ops' own ``op_name`` paths beside the inferred one.

  python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

The harness keeps neither the trace nor the compiled step's text past its
run, so the script wraps ``harness.build_program``, ``harness.run_cell``
and ``trace.load`` to keep them; the run itself is the harness's,
unchanged.
"""
from __future__ import annotations

import re

import numpy as np

FAMILIES = ("dif.step", "dif.model")
UNSCOPED = "unscoped"
PRODUCE_SPAN = "dif.pipeline.produce"
# phases whose ops run the model, for the model family's coverage
MODEL_PHASES = ("inner_adapt", "outer_grad", "hvp")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_SCOPE = {f: re.compile(re.escape(f) + r"\.(\w+)") for f in FAMILIES}


def scopes_of(op_name: str) -> dict:
    """``{family: scope}``: the innermost scope of each family on the path
    ``op_name``, without the family's prefix."""
    out = {}
    for family, pattern in _SCOPE.items():
        found = pattern.findall(op_name)
        if found:
            out[family] = found[-1]
    return out


def op_scopes(hlo_text: str, infer: bool = True) -> dict:
    """``{instruction name: {family: scope}}`` of the HLO text
    ``hlo_text``, for every instruction that has a scope.

    An instruction whose ``op_name`` is a path (``jit(step)/...``) has the
    innermost scope of each family on it, or none.  The compiler leaves no
    metadata, or a bare name, on some of the copies, broadcasts and fusions
    its passes make; with ``infer`` such an instruction takes, family by
    family, the scope that the instructions calling its computation agree
    on (a loop's body takes the loop's), failing that the scope its users
    agree on (a layout copy, or a buffer's initial value, serves what reads
    it), and failing that, as the copies into the step's outputs do, the
    scope its operands agree on.
    """
    own, computation, callers, users, operands = {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            header = _COMPUTATION.match(line)
            comp = header.group(1) if header else None
            continue
        m = _INSTRUCTION.match(line) if comp is not None else None
        if m is None:
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        own[name] = (scopes_of(meta.group(1))
                     if meta and "/" in meta.group(1) else None)
        computation[name] = comp
        head = line.split(", metadata=")[0]
        for one, many in _CALLEE.findall(head):
            for callee in ([one] if one else re.findall(r"%?([\w.\-]+)",
                                                         many)):
                callers.setdefault(callee, []).append(name)
        operands[name] = re.findall(
            r"%([\w.\-]+)", head[m.end():].partition("(")[2].split("), ")[0])
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
    if not infer:
        return {n: found for n, found in own.items() if found}

    from_callers: dict = {}

    def resolve(name: str) -> dict:
        if own[name] is not None:
            return own[name]
        home = computation[name]
        if home not in from_callers:
            from_callers[home] = agreed([resolve(c)
                                         for c in callers.get(home, [])])
        return from_callers[home]

    out = {name: resolve(name) for name in own}
    unnamed = [name for name in own if own[name] is None]
    for neighbours in (users, operands):
        changed = True
        while changed:   # a chain of unnamed ops takes one link a round
            changed = False
            for name in unnamed:
                near = [out[n] for n in neighbours.get(name, ()) if n in out]
                more = {f: s for f, s in agreed(near).items()
                        if f not in out[name]}
                if more:
                    out[name] = {**out[name], **more}
                    changed = True
    return {n: found for n, found in out.items() if found}


def agreed(seen: list) -> dict:
    """The ``{family: scope}`` pairs that every mapping of ``seen`` has."""
    if not seen:
        return {}
    return {f: s for f, s in seen[0].items()
            if all(other.get(f) == s for other in seen[1:])}


def scope_times(trace, scopes: dict) -> dict:
    """``{(step scope, model scope): seconds}`` of device self time in the
    trace's window, averaged over devices; ``trace`` is what
    ``bench.trace.load`` returns and ``scopes`` what :func:`op_scopes`
    returns."""
    from bench import trace as tracing
    windows = [(s, e) for n, s, e in trace.spans if n == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {tracing.WINDOW_SPAN} host span")
    lo, hi = windows[0]
    out: dict = {}
    for all_ops in trace.devices.values():
        ops = tracing._clip(all_ops, lo, hi)
        own = np.bincount(ops.idx, weights=tracing.self_times(ops.start,
                                                              ops.end),
                          minlength=len(ops.names))
        for k in np.flatnonzero(own):
            found = scopes.get(ops.names[k], {})
            key = tuple(found.get(f, UNSCOPED) for f in FAMILIES)
            out[key] = out.get(key, 0.0) + float(own[k]) * 1e-9
    nd = max(1, len(trace.devices))
    return {k: v / nd for k, v in out.items()}


def by_family(times: dict) -> dict:
    """``{family: {scope: seconds}}`` from :func:`scope_times`."""
    out: dict = {f: {} for f in FAMILIES}
    for key, t in times.items():
        for f, scope in zip(FAMILIES, key):
            out[f][scope] = out[f].get(scope, 0.0) + t
    return out


def model_coverage(times: dict) -> float | None:
    """Share of the time under the phases that run the model that a model
    scope names; ``None`` with no such time."""
    under = {k: t for k, t in times.items() if k[0] in MODEL_PHASES}
    total = sum(under.values())
    if not total:
        return None
    return sum(t for k, t in under.items() if k[1] != UNSCOPED) / total


def program_spans(path: str) -> list:
    """``[(name, start_ns, end_ns)]`` of the host events named ``dif.*``
    in the ``.xplane.pb`` at ``path``, on every host thread."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("dif.")]
    return out


def span_time(spans: list, name: str, lo: int, hi: int) -> tuple:
    """``(seconds, count)`` of the spans ``name`` that overlap
    ``[lo, hi)``, clipped to it."""
    inside = [(max(s, lo), min(e, hi)) for n, s, e in spans
              if n == name and e > lo and s < hi]
    return sum(e - s for s, e in inside) * 1e-9, len(inside)


def per_step(trace, spans: list, hlo_text: str, steps: int) -> dict:
    """A traced run's readings over its ``steps`` traced steps, from the
    trace (``bench.trace.load``), the program's host spans
    (:func:`program_spans`) and the compiled step's HLO text:

    * ``scope_ms``: device ms a step by (step scope, model scope), mean over
      the chips (:func:`scope_times`);
    * ``produce_ms``: the producer thread's ms a step, ``None`` where no
      such span ran in the window;
    * ``notes``: each family's unscoped ms a step and the coverages.
    """
    from bench import trace as tracing
    times = scope_times(trace, op_scopes(hlo_text))
    window = next((s, e) for n, s, e in trace.spans
                  if n == tracing.WINDOW_SPAN)
    produce_s, produce_n = span_time(spans, PRODUCE_SPAN, *window)
    fam = by_family(times)
    ms = lambda t: 1e3 * t / steps
    return {"scope_ms": {k: ms(t) for k, t in times.items()},
            "produce_ms": ms(produce_s) if produce_n else None,
            "notes": {"unscoped_ms": {f: ms(fam[f].get(UNSCOPED, 0.0))
                                      for f in FAMILIES},
                      "step_coverage": step_coverage(times),
                      "model_coverage": model_coverage(times)}}


def ms_under(run, family: str, scope: str) -> float | None:
    """A run's device ms a step under ``scope`` of ``family`` (each of the
    family's scopes summed over the other family's), ``None`` where the
    run read no scopes or none of that name."""
    if run.scope_ms is None:
        return None
    return by_family(run.scope_ms)[family].get(scope)


def step_coverage(times: dict) -> float | None:
    """Share of the device's busy time that a phase scope names."""
    busy = sum(times.values())
    if not busy:
        return None
    return 1 - by_family(times)["dif.step"].get(UNSCOPED, 0.0) / busy


def split(result: dict, times: dict, spans: list, window: tuple,
          named: dict | None = None) -> dict:
    """The line the script prints: the run's traced steps' device time by
    phase and by block in ms a step, coverage (also by the ops' own
    ``op_name`` paths alone, ``named``), the producer thread's time, and
    host ms a step in the traced tail and in the untraced steps before
    it."""
    notes = result["notes"]
    traced, steps = notes["traced_steps"], notes["steps"]
    fam = by_family(times)
    busy = sum(times.values())
    tail_s = result["device"]["window_s"]
    produce_s, produce_n = span_time(spans, PRODUCE_SPAN, *window)
    ms = lambda d: {k: 1e3 * v / traced for k, v in sorted(d.items())}
    return {
        "phase_ms": ms(fam["dif.step"]),
        "block_ms": ms(fam["dif.model"]),
        "phase_block_ms": {f"{a}/{b}": 1e3 * t / traced
                           for (a, b), t in sorted(times.items())},
        "busy_ms": 1e3 * busy / traced,
        "step_coverage": step_coverage(times),
        "model_coverage": model_coverage(times),
        "named_step_coverage": step_coverage(named) if named else None,
        "named_model_coverage": model_coverage(named) if named else None,
        "produce_ms": 1e3 * produce_s / traced,
        "produce_n": produce_n,
        "traced_step_ms": 1e3 * tail_s / traced,
        "untraced_step_ms": (1e3 * (notes["window_s"] - tail_s)
                             / (steps - traced) if steps > traced else None),
        "traced_steps": traced,
    }


def main(argv=None) -> int:
    import json
    import pathlib
    import sys
    from unittest import mock

    checkout = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(checkout))
    from bench import harness, run
    from bench import trace as tracing

    kept: dict = {}
    build, run_cell, load = (harness.build_program, harness.run_cell,
                             tracing.load)

    def keep_program(*a, **k):
        kept["program"] = build(*a, **k)
        return kept["program"]

    def keep_result(*a, **k):
        kept["result"] = run_cell(*a, **k)
        return kept["result"]

    def keep_trace(path):
        kept["trace"], kept["spans"] = load(path), program_spans(path)
        return kept["trace"]

    with mock.patch.object(harness, "build_program", keep_program), \
            mock.patch.object(harness, "run_cell", keep_result), \
            mock.patch.object(tracing, "load", keep_trace):
        rc = run.main(list(argv if argv is not None else sys.argv[1:])
                      + ["--trace", "1"])
    if rc or "trace" not in kept:
        return rc or 1
    trace = kept["trace"]
    window = next((s, e) for n, s, e in trace.spans
                  if n == tracing.WINDOW_SPAN)
    text = kept["program"].step.as_text()
    times, named = (scope_times(trace, op_scopes(text, infer))
                    for infer in (True, False))
    print(json.dumps(split(kept["result"], times, kept["spans"], window,
                           named)), flush=True)
    return 0


if __name__ == "__main__":
    import os
    import sys
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
