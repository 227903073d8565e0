"""Tests of the split of device time by the program's named scopes.

On a synthetic HLO text and trace, and on a small scoped trace recorded on
a v5e chip (``record_scoped_trace.py``), with the compiled step's HLO text
beside it.  No chip is needed.
"""
from __future__ import annotations

import io
import json
import pathlib
import sys
from contextlib import redirect_stdout

import pytest

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench import harness, scopes, trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000

HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(step)/dif.step.hvp/tanh"}
}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  ROOT %add.1 = f32[8]{0} add(%fusion.2, %p), metadata={op_name="add"}
}

%cond (c: f32[8]) -> pred[] {
  %c = f32[8]{0} parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main (a: f32[8]) -> (f32[], f32[8]) {
  %a = f32[8]{0} parameter(0)
  %copy.5 = f32[8]{0} copy(%a), metadata={op_name="state.params"}
  %fusion.1 = f32[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/vmap(dif.step.inner_adapt)/jvp(dif.model.mixer)/transpose(dif.step.hvp)/dif.model.ffn/dot_general"}
  %while.3 = f32[8]{0} while(%fusion.1), condition=%cond, body=%body, metadata={op_name="jit(step)/vmap(vmap(dif.step.outer_grad))/dif.model.head/while"}
  %fusion.4 = f32[] fusion(%while.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/reduce_sum"}
  %copy.6 = f32[8]{0} copy(%while.3)
  ROOT %tuple.7 = (f32[], f32[8]{0}) tuple(%fusion.4, %copy.6)
}
"""


def _trace(ops, window=(0, 10 * MS)):
    return trace.Trace({"/device:TPU:0": trace.Ops.of(ops)}, {},
                       [("bench.window", *window)])


def test_op_scopes_take_the_innermost_of_each_family():
    s = scopes.op_scopes(HLO)
    assert s["fusion.1"] == {"dif.step": "hvp", "dif.model": "ffn"}
    assert "fusion.4" not in s
    assert s["while.3"] == {"dif.step": "outer_grad", "dif.model": "head"}


def test_a_loop_body_without_metadata_takes_the_loops_scopes():
    """The compiler leaves some ops no metadata, or a bare op name; ops of
    a loop's body then count under the loop's scopes."""
    s = scopes.op_scopes(HLO)
    assert s["fusion.2"] == s["add.1"] == {"dif.step": "outer_grad",
                                           "dif.model": "head"}
    # a computation whose callers disagree takes no scope from them
    assert s["tanh.1"] == {"dif.step": "hvp"}
    assert set(scopes.op_scopes(HLO, infer=False)) == {
        "tanh.1", "fusion.1", "while.3"}


def test_an_unnamed_op_takes_its_users_scopes_else_its_operands():
    """A layout copy serves what reads it; a copy into the step's outputs
    takes what it copies.  An op the program made outside every scope (its
    path names none) stays unscoped whatever its neighbours are."""
    s = scopes.op_scopes(HLO)
    assert s["copy.5"] == {"dif.step": "hvp", "dif.model": "ffn"}
    assert s["copy.6"] == s["while.3"]
    assert "fusion.4" not in s and "tuple.7" not in s


NESTED_HLO = """\
ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/dif.step.outer_grad/dif.model.ffn/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/dif.step.outer_grad/dif.model.ffn/dif.model.moe_route/dot_general"}
  ROOT %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={op_name="jit(step)/vmap(dif.step.hvp)/jvp(dif.model.ffn)/transpose(dif.model.moe_experts)/ragged_dot"}
}
"""


def test_a_model_scope_nested_in_ffn_takes_its_ops():
    """A ``dif.model.<x>`` scope inside ``dif.model.ffn``, as a router's or
    an expert layer's would be, takes its ops under ``<x>`` and out of
    ``ffn``; ``ffn_ms`` then reads the block's other ops alone, and the
    family's times still add up to the busy time."""
    assert scopes.scopes_of("jit(step)/dif.step.hvp/dif.model.ffn/"
                            "dif.model.moe_route/dot") == {
        "dif.step": "hvp", "dif.model": "moe_route"}
    t = _trace([("fusion.1", 0, 1 * MS), ("fusion.2", 1 * MS, 3 * MS),
                ("fusion.3", 3 * MS, 7 * MS)])
    times = scopes.scope_times(t, scopes.op_scopes(NESTED_HLO))
    assert times == {("outer_grad", "ffn"): pytest.approx(0.001),
                     ("outer_grad", "moe_route"): pytest.approx(0.002),
                     ("hvp", "moe_experts"): pytest.approx(0.004)}
    run = _run(scope_ms={k: 1e3 * v / 4 for k, v in times.items()})
    assert harness.load_reader("ffn_ms")(run) == pytest.approx(0.25)
    assert scopes.ms_under(run, "dif.model", "moe_route") == \
        pytest.approx(0.5)
    assert scopes.ms_under(run, "dif.model", "moe_experts") == \
        pytest.approx(1.0)
    assert sum(times.values()) == pytest.approx(trace.summarize(t).busy_s)


def test_scope_times_count_self_time_under_a_loop_and_the_unscoped():
    t = _trace([("while.3", 0, 8 * MS), ("fusion.2", 1 * MS, 3 * MS),
                ("add.1", 3 * MS, 4 * MS), ("fusion.1", 8 * MS, 9 * MS),
                ("fusion.4", 9 * MS, 11 * MS)])
    times = scopes.scope_times(t, scopes.op_scopes(HLO))
    assert times == {("outer_grad", "head"): pytest.approx(0.008),
                     ("hvp", "ffn"): pytest.approx(0.001),
                     ("unscoped", "unscoped"): pytest.approx(0.001)}
    fam = scopes.by_family(times)
    assert fam["dif.step"] == {"outer_grad": pytest.approx(0.008),
                               "hvp": pytest.approx(0.001),
                               "unscoped": pytest.approx(0.001)}
    assert sum(times.values()) == pytest.approx(trace.summarize(t).busy_s)
    assert scopes.model_coverage(times) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="bench.window"):
        scopes.scope_times(trace.Trace(t.devices, {}, []), {})


def test_split_reads_ms_a_step_coverage_and_the_producer():
    times = {("inner_adapt", "mixer"): 0.003, ("hvp", "unscoped"): 0.001,
             ("outer_update", "unscoped"): 0.002,
             ("unscoped", "unscoped"): 0.002}
    result = {"notes": {"traced_steps": 2, "steps": 6, "window_s": 1.0},
              "device": {"window_s": 0.2}}
    spans = [("dif.pipeline.produce", -5, 5), ("dif.pipeline.produce", 6, 8),
             ("dif.pipeline.produce", 20, 30)]
    out = scopes.split(result, times, spans, (0, 10))
    assert out["phase_ms"] == {"hvp": pytest.approx(0.5),
                               "inner_adapt": pytest.approx(1.5),
                               "outer_update": pytest.approx(1.0),
                               "unscoped": pytest.approx(1.0)}
    assert out["block_ms"]["mixer"] == pytest.approx(1.5)
    assert out["busy_ms"] == pytest.approx(4.0)
    assert out["step_coverage"] == pytest.approx(0.75)
    assert out["model_coverage"] == pytest.approx(0.75)
    assert out["named_step_coverage"] is None
    named = {("unscoped", "unscoped"): 0.006, ("hvp", "unscoped"): 0.002}
    out = scopes.split(result, times, spans, (0, 10), named)
    assert out["named_step_coverage"] == pytest.approx(0.25)
    assert out["named_model_coverage"] == pytest.approx(0.0)
    assert out["produce_n"] == 2
    assert out["produce_ms"] == pytest.approx(7e-9 * 1e3 / 2)
    assert out["traced_step_ms"] == pytest.approx(100.0)
    assert out["untraced_step_ms"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# The recorded scoped trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = str(DATA / "small_scoped.xplane.pb")
    t = trace.load(path)
    s = scopes.op_scopes((DATA / "small_scoped.hlo.txt").read_text())
    return path, t, s


def test_recorded_scoped_trace_adds_up_to_busy_time(recorded):
    """A step with two phases, one under a model block, a loop on the chip
    and an unscoped reduction, dispatched four times on one v5e."""
    _, t, s = recorded
    times = scopes.scope_times(t, s)
    fam = scopes.by_family(times)
    assert fam["dif.step"]["inner_adapt"] > 0 and fam["dif.step"]["hvp"] > 0
    assert fam["dif.model"]["mixer"] > 0
    busy = trace.summarize(t).busy_s
    assert sum(fam["dif.step"].values()) == pytest.approx(busy, rel=0.01)
    assert sum(times.values()) == pytest.approx(busy, rel=0.01)


def test_recorded_producer_spans(recorded):
    path, t, _ = recorded
    spans = scopes.program_spans(path)
    window = next((s, e) for n, s, e in t.spans if n == "bench.window")
    seconds, n = scopes.span_time(spans, scopes.PRODUCE_SPAN, *window)
    assert n == 3 and 0.003 <= seconds < 0.1
    # the harness's own spans stay what they were: no dif.* span among them
    assert not [n for n, _, _ in t.spans if n.startswith("dif.")]


def test_the_split_leaves_the_accepted_reduction_as_it_was(recorded):
    """The accepted metrics and breakdown read the same from a trace that
    the split has read too."""
    def readings():
        t = trace.load(str(DATA / "small.xplane.pb"))
        s = trace.summarize(t)
        run = harness.Run(setup_s=1, window_s=1, steps=4, traced_steps=4,
                          tokens_per_step=1, step_flops=1, chips=1,
                          peaks=None, trace=s)
        return t, s, {m: harness.load_reader(m)(run)
                      for m in ("input_wait_ms", "device_idle_share")}

    t, before, metrics = readings()
    scopes.scope_times(t, recorded[2])
    assert trace.summarize(t) == before
    assert readings()[1:] == (before, metrics)
    assert before.top_ops and before.idle_gaps


def test_the_script_prints_the_harness_line_then_the_split(recorded,
                                                           monkeypatch):
    """The script runs the harness's own run and keeps what it drops: the
    compiled step's text and the trace."""
    path, t, _ = recorded
    hlo = (DATA / "small_scoped.hlo.txt").read_text()
    result = {"notes": {"traced_steps": 4, "steps": 4, "window_s": 0.02},
              "device": {"window_s": 0.02}}

    class Compiled:
        def as_text(self):
            return hlo

    class Program:
        step = Compiled()

    from bench import run

    def fake_main(argv):
        assert argv[-2:] == ["--trace", "1"]
        harness.build_program(None, [])
        print(json.dumps(harness.run_cell(None, [])))
        trace.load(path)
        return 0

    monkeypatch.setattr(harness, "build_program", lambda *a: Program())
    monkeypatch.setattr(harness, "run_cell", lambda *a: result)
    monkeypatch.setattr(run, "main", fake_main)
    out = io.StringIO()
    with redirect_stdout(out):
        assert scopes.main(["--workload", "x", "--seed", "1",
                            "--seconds", "1"]) == 0
    first, last = out.getvalue().strip().splitlines()
    assert json.loads(first) == result
    split = json.loads(last)
    assert set(split["phase_ms"]) >= {"inner_adapt", "hvp"}
    assert split["produce_n"] == 3
    assert split["step_coverage"] > 0.5


# ---------------------------------------------------------------------------
# The per-layer metrics read from the scopes
# ---------------------------------------------------------------------------

SCOPE_READERS = {"inner_adapt_ms": ("phase_ms", "inner_adapt"),
                 "outer_grad_ms": ("phase_ms", "outer_grad"),
                 "hvp_ms": ("phase_ms", "hvp"),
                 "outer_update_ms": ("phase_ms", "outer_update"),
                 "combine_ms": ("phase_ms", "combine"),
                 "mixer_ms": ("block_ms", "mixer"),
                 "ffn_ms": ("block_ms", "ffn"),
                 "vocab_head_ms": ("block_ms", "head"),
                 "produce_ms": ("produce_ms", None)}


def _run(**kw):
    return harness.Run(setup_s=1, window_s=1, steps=4, traced_steps=4,
                       tokens_per_step=1, step_flops=1, chips=1, peaks=None,
                       **kw)


@pytest.fixture(scope="module")
def recorded_reading(recorded):
    """What a traced run of the harness reads from the recorded trace, and
    what the script's split reads from it."""
    path, t, s = recorded
    spans = scopes.program_spans(path)
    hlo = (DATA / "small_scoped.hlo.txt").read_text()
    scoped = scopes.per_step(t, spans, hlo, 4)
    run = _run(trace=trace.summarize(t), scope_ms=scoped["scope_ms"],
               produce_ms=scoped["produce_ms"])
    window = next((a, b) for n, a, b in t.spans if n == "bench.window")
    result = {"notes": {"traced_steps": 4, "steps": 4, "window_s": 0.02},
              "device": {"window_s": run.trace.window_s}}
    split = scopes.split(result, scopes.scope_times(t, s), spans, window)
    return run, scoped, split


@pytest.mark.parametrize("metric", sorted(SCOPE_READERS))
def test_the_scope_readers_on_the_recorded_trace(recorded_reading, metric):
    """Each reader gives its scope's device ms a step as the script's split
    reads it, or nothing where the recorded step has no such scope, and
    nothing from a run that read no scopes."""
    run, _, split = recorded_reading
    read = harness.load_reader(metric)
    key, scope = SCOPE_READERS[metric]
    want = split[key] if scope is None else split[key].get(scope)
    got = read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9) and got > 0
    assert read(_run()) is None


def test_the_recorded_trace_reads_its_scopes_and_notes(recorded_reading):
    _, scoped, split = recorded_reading
    present = {m for m in SCOPE_READERS
               if harness.load_reader(m)(recorded_reading[0]) is not None}
    assert present == {"inner_adapt_ms", "hvp_ms", "mixer_ms", "produce_ms"}
    notes = scoped["notes"]
    assert notes["step_coverage"] == pytest.approx(split["step_coverage"])
    assert notes["unscoped_ms"]["dif.step"] == pytest.approx(
        split["phase_ms"]["unscoped"])
    assert sum(scoped["scope_ms"].values()) == pytest.approx(
        split["busy_ms"])


def test_a_scope_nested_in_a_recorded_block_takes_its_time(recorded,
                                                         recorded_reading):
    """The recorded step's text with ``dif.model.moe_experts`` nested
    inside each ``dif.model.mixer``: the nested scope takes all that the
    block read, inferred ops included, and the block reads nothing."""
    path, t, _ = recorded
    run, scoped, _ = recorded_reading
    hlo = (DATA / "small_scoped.hlo.txt").read_text()
    nested = hlo.replace("dif.model.mixer/",
                         "dif.model.mixer/dif.model.moe_experts/")
    assert nested != hlo
    got = scopes.per_step(t, scopes.program_spans(path), nested, 4)
    moved = _run(scope_ms=got["scope_ms"])
    assert harness.load_reader("mixer_ms")(moved) is None
    assert scopes.ms_under(moved, "dif.model", "moe_experts") == \
        pytest.approx(harness.load_reader("mixer_ms")(run), rel=1e-9)
    assert sum(got["scope_ms"].values()) == pytest.approx(
        sum(scoped["scope_ms"].values()), rel=1e-9)


def test_collective_exposed_ms_on_four_chips():
    """The exposed part of the collectives, ms a step, mean over four
    devices; nothing to read where no collective ran."""
    devices, asyncs = {}, {}
    for k in range(4):
        plane = f"/device:TPU:{k}"
        # on chip k, k ms of a 4 ms permute run beside other work
        devices[plane] = trace.Ops.of([("fusion.1", 0, k * MS)])
        asyncs[plane] = trace.Ops.of([("collective-permute-start.2", 0,
                                       4 * MS)])
    t = trace.Trace(devices, asyncs, [("bench.window", 0, 10 * MS)])
    read = harness.load_reader("collective_exposed_ms")
    run = _run(trace=trace.summarize(t))
    assert run.trace.n_devices == 4
    # exposed 4, 3, 2, 1 ms over 4 traced steps
    assert read(run) == pytest.approx((4 + 3 + 2 + 1) / 4 / 4)
    quiet = trace.Trace({"/device:TPU:0": trace.Ops.of([("fusion.1", 0,
                                                         MS)])},
                        {}, [("bench.window", 0, 10 * MS)])
    assert read(_run(trace=trace.summarize(quiet))) is None
    assert read(_run()) is None
