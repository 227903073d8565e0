"""repro.analysis: rule registry, HLO structure parsing, mutation self-tests.

Convention (see ANALYSIS.md): every rule ships with at least one *mutation*
test — a deliberately broken lowering (doctored HLO, a mis-traced jaxpr, or
an over-counting jit cache) the rule must flag — next to the clean fixture
it must pass.  A rule without a mutation test is assumed vacuous.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo as H
from repro.analysis.rules import (
    RULES,
    CompileCounter,
    Finding,
    LintContext,
    combine_window,
    register_rule,
    run_rules,
)
from repro.configs import get_config
from repro.core import (
    MetaConfig,
    TopologyConfig,
    UpdateConfig,
    init_state,
    make_meta_step,
)
from repro.data import SineTaskSource
from repro.launch import steps as S
from repro.models.simple import SineMLP


# ---------------------------------------------------------------------------
# Handcrafted HLO fixtures (K=4 ring, deg=2, shard = 1000 u16 elems = 2000 B)
# ---------------------------------------------------------------------------

_K4_WIRE_HLO = textwrap.dedent("""
    HloModule wire_fixture

    ENTRY %main (p0: f32[16]) -> f32[16] {
      %p0 = f32[16]{0} parameter(0)
      %cp0 = u16[1000]{0} collective-permute(%x0), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
      %cp1 = u16[1000]{0} collective-permute(%x1), source_target_pairs={{0,3},{1,0},{2,1},{3,2}}
      %cpr = f32[300]{0} collective-permute(%x2), source_target_pairs={{0,1},{1,0}}
    }
""")

_COND_HLO = textwrap.dedent("""
    HloModule cond_fixture, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }

    %noop_branch (np0: u16[1000]) -> u16[1000] {
      %np0 = u16[1000]{0} parameter(0)
      ROOT %ncopy = u16[1000]{0} copy(%np0)
    }

    %combine_branch (cp0.p: u16[1000]) -> u16[1000] {
      %cp0.p = u16[1000]{0} parameter(0)
      %A = f32[4,4]{1,0} constant({...})
      %W = f32[4,16]{1,0} constant({...})
      %mix = f32[4,16]{1,0} dot(%A, %W), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %w0 = u16[1000]{0} collective-permute(%cp0.p), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
      ROOT %w1 = u16[1000]{0} collective-permute(%w0), source_target_pairs={{0,3},{1,0},{2,1},{3,2}}
    }

    ENTRY %main (e0: u16[1000], epred: pred[]) -> u16[1000] {
      %e0 = u16[1000]{0} parameter(0)
      %epred = pred[] parameter(1)
      ROOT %gate = u16[1000]{0} conditional(%epred, %e0, %e0), branch_computations={%noop_branch, %combine_branch}
    }
""")


def _wire_ctx(hlo, **kw):
    base = dict(hlo=hlo, n_dev=4, K=4, degree=2, shard_bytes=2000,
                wire_dtype="bfloat16")
    base.update(kw)
    return LintContext(**base)


# ---------------------------------------------------------------------------
# collective-budget
# ---------------------------------------------------------------------------


def test_collective_budget_clean_fixture_passes_and_records():
    rep = run_rules(_wire_ctx(_K4_WIRE_HLO), only=["collective-budget"])
    assert rep.checked == ["collective-budget"] and rep.ok
    rec = rep.records["collective-budget"]
    # the window reads the u16 slice only — resharding f32 bytes excluded
    assert rec["permute_bytes"] == 2 * 2000
    assert rec["all_permute_bytes"] == 2 * 2000 + 300 * 4
    assert rec["expected_permute_bytes"] == 2 * 2000


def test_collective_budget_flags_missing_combine_mutation():
    # mutation: shrink the combine permutes 4× — wire below deg·shard
    broken = _K4_WIRE_HLO.replace("u16[1000]", "u16[250]")
    rep = run_rules(_wire_ctx(broken), only=["collective-budget"])
    assert not rep.ok and "below" in rep.findings[0].message


def test_collective_budget_flags_k_scaling_mutation():
    # mutation: the dense all-gather pattern — permutes ship 4× the shard
    broken = _K4_WIRE_HLO.replace("u16[1000]", "u16[4000]")
    rep = run_rules(_wire_ctx(broken), only=["collective-budget"])
    assert not rep.ok and "above" in rep.findings[0].message


def test_collective_budget_flags_ceiling_mutation():
    rep = run_rules(_wire_ctx(_K4_WIRE_HLO, budget_ceiling=100),
                    only=["collective-budget"])
    assert not rep.ok
    assert any("ceiling" in f.message for f in rep.findings)
    # the window itself is still clean — exactly one finding
    assert len(rep.findings) == 1


def test_combine_window_totals_match_hlo():
    rec = combine_window(_K4_WIRE_HLO, 4, degree=2, shard_bytes=2000,
                         wire_dtype="bfloat16")
    assert rec["ok"] and rec["permute_count"] == 3
    assert rec["total_collective_bytes"] == 2 * 2000 + 300 * 4


# ---------------------------------------------------------------------------
# wire-dtype-leak
# ---------------------------------------------------------------------------


def test_wire_dtype_leak_clean_fixture_passes():
    rep = run_rules(_wire_ctx(_K4_WIRE_HLO), only=["wire-dtype-leak"])
    assert rep.checked == ["wire-dtype-leak"] and rep.ok


def test_wire_dtype_leak_flags_full_width_mutation():
    # mutation: the u16 bitcast dropped — payload rides as f32
    broken = _K4_WIRE_HLO.replace("u16[1000]", "f32[1000]")
    rep = run_rules(_wire_ctx(broken), only=["wire-dtype-leak"])
    assert not rep.ok
    assert "no u16 collective-permute traffic" in rep.findings[0].message


def test_wire_dtype_leak_flags_partial_leak_mutation():
    # mutation: one of the two combine rounds leaked to full width
    broken = _K4_WIRE_HLO.replace("%cp1 = u16[1000]", "%cp1 = f32[1000]")
    rep = run_rules(_wire_ctx(broken), only=["wire-dtype-leak"])
    assert not rep.ok and "leaked" in rep.findings[0].message


def test_wire_dtype_leak_skipped_without_bf16_wire():
    rep = run_rules(_wire_ctx(_K4_WIRE_HLO, wire_dtype="float32"),
                    only=["wire-dtype-leak"])
    assert rep.skipped == ["wire-dtype-leak"] and rep.checked == []


# ---------------------------------------------------------------------------
# conditional-comm
# ---------------------------------------------------------------------------


def _cond_ctx(hlo):
    return LintContext(hlo=hlo, K=4, combine_every=2,
                       wire_dtype="bfloat16")


def test_conditional_comm_clean_fixture_passes():
    rep = run_rules(_cond_ctx(_COND_HLO), only=["conditional-comm"])
    assert rep.checked == ["conditional-comm"] and rep.ok


def test_conditional_comm_flags_unconditional_mutation():
    # mutation: a combine dot hoisted into ENTRY — skipped steps pay it
    broken = _COND_HLO.replace(
        "%epred = pred[] parameter(1)",
        "%epred = pred[] parameter(1)\n"
        "  %hoist = f32[4,16]{1,0} dot(%A, %W)")
    rep = run_rules(_cond_ctx(broken), only=["conditional-comm"])
    assert not rep.ok
    assert any("unconditionally" in f.message for f in rep.findings)


def test_conditional_comm_flags_both_branches_hot_mutation():
    # mutation: the "skip" branch also permutes — the gate is vacuous
    broken = _COND_HLO.replace(
        "ROOT %ncopy = u16[1000]{0} copy(%np0)",
        "ROOT %ncopy = u16[1000]{0} collective-permute(%np0), "
        "source_target_pairs={{0,1},{1,0}}")
    rep = run_rules(_cond_ctx(broken), only=["conditional-comm"])
    assert not rep.ok
    assert any("branches" in f.message for f in rep.findings)


def test_conditional_comm_flags_unlowered_combine_mutation():
    # mutation: no K×K dot, no wire permutes anywhere — combine vanished
    broken = (_COND_HLO
              .replace("u16[1000]{0} collective-permute", "u16[1000]{0} copy")
              .replace(" dot(", " mul("))
    rep = run_rules(_cond_ctx(broken), only=["conditional-comm"])
    assert not rep.ok
    assert "not lowered at all" in rep.findings[0].message


def test_conditional_comm_flags_ungated_orphan_mutation():
    # mutation: the conditional is gone; markers exist but nothing gates them
    broken = _COND_HLO.replace(
        "ROOT %gate = u16[1000]{0} conditional(%epred, %e0, %e0), "
        "branch_computations={%noop_branch, %combine_branch}",
        "ROOT %gate = u16[1000]{0} copy(%e0)")
    rep = run_rules(_cond_ctx(broken), only=["conditional-comm"])
    assert not rep.ok
    assert any("no conditional gates" in f.message for f in rep.findings)


# ---------------------------------------------------------------------------
# donation-honored
# ---------------------------------------------------------------------------


def test_donation_honored_on_real_lowerings():
    def f(state, x):
        return (jax.tree.map(lambda a: a + x.sum(), state), x * 2)

    state = {"a": jax.ShapeDtypeStruct((128,), jnp.float32),
             "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    good = jax.jit(f, donate_argnums=(0,)).lower(state, x).compile().as_text()
    rep = run_rules(LintContext(hlo=good, expected_aliases=2),
                    only=["donation-honored"])
    assert rep.checked == ["donation-honored"] and rep.ok
    assert rep.records["donation-honored"]["alias_entries"] >= 2
    # mutation: same program compiled WITHOUT donation — no aliases
    bad = jax.jit(f).lower(state, x).compile().as_text()
    rep_bad = run_rules(LintContext(hlo=bad, expected_aliases=2),
                        only=["donation-honored"])
    assert not rep_bad.ok
    assert "defensive copies" in rep_bad.findings[0].message


def test_donation_honored_fraction_threshold_on_fixture():
    # _COND_HLO's header declares exactly 2 alias entries
    ok = run_rules(LintContext(hlo=_COND_HLO, expected_aliases=2),
                   only=["donation-honored"])
    assert ok.ok
    short = run_rules(LintContext(hlo=_COND_HLO, expected_aliases=4),
                      only=["donation-honored"])
    assert not short.ok
    assert short.records["donation-honored"]["required"] == 4


# ---------------------------------------------------------------------------
# retrace-guard
# ---------------------------------------------------------------------------


def test_retrace_guard_clean_trace_passes():
    jaxpr = jax.make_jaxpr(lambda x, s: x * s)(
        jnp.ones(4), jnp.array(3.0, jnp.float32))
    rep = run_rules(LintContext(jaxpr=jaxpr), only=["retrace-guard"])
    assert rep.checked == ["retrace-guard"] and rep.ok


def test_retrace_guard_flags_weak_type_scalar_mutation():
    # mutation: a python float leaks into the trace as a weak-typed invar
    jaxpr = jax.make_jaxpr(lambda x, s: x * s)(jnp.ones(4), 3.0)
    rep = run_rules(LintContext(jaxpr=jaxpr), only=["retrace-guard"])
    assert not rep.ok and "weak-typed" in rep.findings[0].message


def test_retrace_guard_flags_host_callback_mutation():
    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), jnp.float32),
            x)
        return y + 1.0

    jaxpr = jax.make_jaxpr(f)(jnp.ones(4))
    rep = run_rules(LintContext(jaxpr=jaxpr), only=["retrace-guard"])
    assert not rep.ok
    assert "pure_callback" in rep.findings[0].message


def test_retrace_guard_flags_compile_count_overrun():
    counts = {"superstep": {"compiles": 3, "expected": 1, "dispatches": 8}}
    rep = run_rules(LintContext(compile_counts=counts),
                    only=["retrace-guard"])
    assert not rep.ok and "compiled 3×" in rep.findings[0].message
    # unknown cache sizes are tolerated, not treated as violations
    rep_none = run_rules(
        LintContext(compile_counts={"s": {"compiles": None, "expected": 1}}),
        only=["retrace-guard"])
    assert rep_none.ok


def test_compile_counter_reads_jit_cache():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4))
    c = CompileCounter(f)
    assert c.count() == 1
    f(jnp.ones(8))  # new shape → second compile
    assert c.count() == 2
    assert CompileCounter(object()).count() is None


# ---------------------------------------------------------------------------
# recompile-count regressions (the invariant behind the superstep driver)
# ---------------------------------------------------------------------------


def test_superstep_c8_compiles_exactly_once_across_dispatches():
    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    K, C = 4, 8
    mcfg = MetaConfig(num_agents=K, tasks_per_agent=2, inner_lr=0.01,
                      outer_optimizer="sgd", outer_lr=5e-3,
                      update_config=UpdateConfig(strategy="atc"),
                      topology_config=TopologyConfig(graph="ring",
                                                     schedule="gossip",
                                                     seed=0))
    meta = make_meta_step(model.loss_fn, mcfg)

    def step_fn(st, b):
        return meta(st, b["support"], b["query"])

    source = SineTaskSource(K=K, tasks_per_agent=2, shots=5, seed=0)
    state = init_state(jax.random.key(0), model.init, mcfg)
    superstep = jax.jit(S.make_superstep(step_fn))
    for d in range(2):
        chunk = []
        for i in range(C):
            ep = source.sample(d * C + i)
            chunk.append({"support": jax.tree.map(jnp.asarray, ep.support),
                          "query": jax.tree.map(jnp.asarray, ep.query)})
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *chunk)
        state, _ = superstep(state, stacked)
    compiles = CompileCounter(superstep).count()
    assert compiles == 1, (
        f"superstep compiled {compiles}× across 2 same-shape dispatches — "
        f"something in the carried state retriggers tracing")


_DYNAMIC_RECOMPILE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.analysis.rules import CompileCounter
    from repro.core import diffusion, topology

    K, M = 8, 256
    mesh = compat.make_mesh((K,), ("data",))
    phi = {"w": jax.device_put(jnp.ones((K, M), jnp.float32),
                               NamedSharding(mesh, P("data", None)))}
    topo = topology.build_topology("ring", K)
    sched = topology.make_schedule("link_failure", topo, p=0.3, period=8,
                                   seed=0)
    with mesh:
        fn = jax.jit(diffusion.make_combine(
            "mesh_sparse_dynamic", A=sched.matrices, mesh=mesh,
            axis_name="data", in_specs={"w": P("data", None)}))
        for step in range(16):
            phi = fn(phi, jnp.asarray(step, jnp.int32))
        compiles = CompileCounter(fn).count()
    print("RECOMPILE_JSON:" + json.dumps(
        {"compiles": compiles, "dispatches": 16}))
""")


def test_mesh_sparse_dynamic_compiles_once_across_schedule():
    """16 steps across two periods of a link_failure schedule must hit one
    jit cache entry: the schedule is a traced gather, not a python branch."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _DYNAMIC_RECOMPILE_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("RECOMPILE_JSON:")]
    assert lines, res.stderr[-2000:]
    out = json.loads(lines[0][len("RECOMPILE_JSON:"):])
    assert out["compiles"] == 1, out


# ---------------------------------------------------------------------------
# hlo.py structure parsing
# ---------------------------------------------------------------------------


def test_parse_computations_and_entry():
    comps, entry = H.parse_computations(_COND_HLO)
    assert entry == "main"
    assert set(comps) == {"main", "noop_branch", "combine_branch"}
    assert len(comps["combine_branch"]) == 6
    # the printed HLO names operands without their types: rules resolve
    # them through the module's definitions
    shapes = H.instruction_shapes(comps)
    assert shapes["A"] == "f32[4,4]" and shapes["cp0.p"] == "u16[1000]"
    mix = next(l for l in comps["combine_branch"] if " dot(" in l)
    assert H.operand_names(mix, "dot") == ["A", "W"]


def test_reachable_stops_at_branches():
    comps, entry = H.parse_computations(_COND_HLO)
    assert H.reachable(comps, entry) == {"main", "noop_branch",
                                         "combine_branch"}
    assert H.reachable(comps, entry, include_branches=False) == {"main"}


def test_conditional_branch_forms():
    line = ("%c = f32[] conditional(%p, %a, %b), "
            "true_computation=%yes, false_computation=%no")
    assert H.conditional_branches(line) == ["yes", "no"]
    [gate] = H.conditional_lines(H.parse_computations(_COND_HLO)[0])
    assert H.conditional_branches(gate) == ["noop_branch", "combine_branch"]


def test_alias_entries_brace_matching():
    assert H.alias_entries(_COND_HLO) == 2
    assert H.alias_entries(_K4_WIRE_HLO) == 0


# ---------------------------------------------------------------------------
# registry plumbing
# ---------------------------------------------------------------------------


def test_registry_has_all_five_rules():
    assert set(RULES) >= {"collective-budget", "wire-dtype-leak",
                          "conditional-comm", "donation-honored",
                          "retrace-guard"}


def test_empty_context_skips_everything():
    rep = run_rules(LintContext())
    assert rep.checked == [] and set(rep.skipped) == set(RULES)
    assert rep.ok  # no rule ran, no finding — callers see skipped, not fail


def test_report_json_roundtrip():
    rep = run_rules(_wire_ctx(_K4_WIRE_HLO.replace("u16[1000]", "u16[250]")),
                    only=["collective-budget"])
    j = json.loads(json.dumps(rep.to_json()))
    assert j["ok"] is False and j["findings"][0]["rule"] == "collective-budget"
    assert j["records"]["collective-budget"]["permute_bytes"] == 2 * 500


def test_register_rule_and_only_selection():
    try:
        @register_rule("tmp-always", "test-only rule", lambda ctx: True)
        def _tmp(ctx):
            return [Finding("tmp-always", "fired")]

        rep = run_rules(LintContext(), only=["tmp-always"])
        assert [f.rule for f in rep.findings] == ["tmp-always"]
    finally:
        RULES.pop("tmp-always", None)


def test_every_registered_rule_has_a_description():
    for rule in RULES.values():
        assert rule.description and rule.name
