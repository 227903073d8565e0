"""DiffusionStrategy × CommSchedule × TopologySchedule composition.

Covers the first-class decentralized-update API: strategy registry parity
against hand-written compositions, the nested MetaConfig surface (flat
fields as deprecated aliases), the lax.cond communication gating (skipped
steps execute no combine matmul — checked on the optimized HLO), and
stacked matrix schedules through the combine backends.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (MetaConfig, TopologyConfig, UpdateConfig, diffusion,
                        init_state, make_meta_step, maml, topology, update)
from repro.core.meta_trainer import (combination_matrix_for, schedule_for,
                                     topology_for)
from repro.data import SineTaskSource
from repro.models.simple import SineMLP
from repro.optim import get_optimizer

K = 6


@pytest.fixture(scope="module")
def sine_model():
    return SineMLP(get_config("sine_mlp"))


@pytest.fixture(scope="module")
def episodes():
    src = SineTaskSource(K=K, tasks_per_agent=2, shots=10, seed=0)
    eps = [src.sample(i) for i in range(4)]
    return [(jax.tree.map(jnp.asarray, e.support),
             jax.tree.map(jnp.asarray, e.query)) for e in eps]


def _phi(seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w": jax.random.normal(k1, (K, 7, 5)),
            "b": jax.random.normal(k2, (K, 3))}


def _nested(strategy, schedule="static", graph="ring", every=1, **kw):
    return MetaConfig(
        num_agents=K, tasks_per_agent=2, inner_lr=0.01,
        outer_optimizer="sgd", outer_lr=5e-3,
        update_config=UpdateConfig(strategy=strategy, combine_every=every),
        topology_config=TopologyConfig(graph=graph, schedule=schedule, **kw))


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

def test_strategy_registry_contents():
    names = update.update_strategies()
    for expected in ("atc", "cta", "consensus", "none", "centralized"):
        assert expected in names
    with pytest.raises(ValueError, match="registered"):
        update.get_strategy("bogus")
    assert update.get_strategy("cta").pre_combine
    assert not update.get_strategy("none").communicates
    assert not update.get_strategy("centralized").needs_combine_fn


def test_inner_algo_registry():
    for expected in ("maml", "fomaml", "reptile"):
        assert expected in update.inner_algos()
    assert update.get_inner_algo("maml").order == 2
    assert update.get_inner_algo("fomaml").order == 1
    with pytest.raises(ValueError, match="registered"):
        update.get_inner_algo("bogus")


def test_comm_schedule():
    always = update.CommSchedule()
    assert always.always
    s = update.CommSchedule(every=3)
    assert not s.always
    assert [bool(s.is_comm_step(i)) for i in range(6)] == [
        False, False, True, False, False, True]
    with pytest.raises(ValueError, match=">= 1"):
        update.CommSchedule(every=0)


# ---------------------------------------------------------------------------
# Strategy compositions == hand-written formulas
# ---------------------------------------------------------------------------

def test_strategy_compositions_match_handwritten():
    A = topology.combination_matrix(K, "ring")
    combine = diffusion.make_combine("dense", A=A)
    params, updates = _phi(0), _phi(1)
    plus = jax.tree.map(lambda p, u: p + u, params, updates)

    atc = update.get_strategy("atc").apply(params, updates, combine, 0)
    ref = diffusion.dense_combine(jnp.asarray(A), plus)
    for a, b in zip(jax.tree.leaves(atc), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    con = update.get_strategy("consensus").apply(params, updates, combine, 0)
    ref = jax.tree.map(lambda m, u: m + u,
                       diffusion.dense_combine(jnp.asarray(A), params),
                       updates)
    for a, b in zip(jax.tree.leaves(con), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    non = update.get_strategy("none").apply(params, updates, None, 0)
    for a, b in zip(jax.tree.leaves(non), jax.tree.leaves(plus)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    cen = update.get_strategy("centralized").apply(params, updates, None, 0)
    ref = jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True),
                                   x.shape), plus)
    for a, b in zip(jax.tree.leaves(cen), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Trainer parity: the assembled step == hand-written compositions, bitwise
# ---------------------------------------------------------------------------

def _run_trainer(model, mcfg, episodes, steps=3):
    state = init_state(jax.random.key(0), model.init, mcfg,
                       identical_init=False)
    step = jax.jit(make_meta_step(model.loss_fn, mcfg))
    for sup, qry in episodes[:steps]:
        state, metrics = step(state, sup, qry)
    return state, metrics


def _run_handwritten(model, strategy, episodes, steps=3):
    """The strategy compositions spelled out with the raw pieces — the
    'current trainer' formulas the new assembly must reproduce bitwise."""
    mcfg = _nested("atc")     # only init/opt hyperparams are read
    opt = get_optimizer("sgd", 5e-3)
    state = init_state(jax.random.key(0), model.init, mcfg,
                       identical_init=False)
    A = jnp.asarray(topology.combination_matrix(K, "ring"))

    def per_agent(p, s, q):
        return maml.multi_task_meta_grad(model.loss_fn, p, s, q, alpha=0.01)

    @jax.jit
    def step(params, opt_state, sup, qry):
        base = params
        if strategy == "cta":
            base = diffusion.dense_combine(A, params)
        losses, grads = jax.vmap(per_agent)(base, sup, qry)
        updates, opt_state = opt.update(grads, opt_state, base)
        if strategy == "atc":
            new = diffusion.atc_step(base, updates,
                                     lambda p: diffusion.dense_combine(A, p))
        elif strategy == "consensus":
            new = diffusion.cta_step(base, updates,
                                     lambda p: diffusion.dense_combine(A, p))
        else:                  # cta: mixed before the gradient, local apply
            new = jax.tree.map(lambda p, u: p + u, base, updates)
        return new, opt_state

    params, opt_state = state.params, state.opt_state
    for sup, qry in episodes[:steps]:
        params, opt_state = step(params, opt_state, sup, qry)
    return params


@pytest.mark.parametrize("strategy", ["atc", "cta", "consensus"])
def test_trainer_bit_identical_to_handwritten(sine_model, episodes, strategy):
    state, _ = _run_trainer(sine_model, _nested(strategy), episodes)
    ref = _run_handwritten(sine_model, strategy, episodes)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flat_config_path_identical_to_nested(sine_model, episodes):
    """Legacy flat MetaConfig(combine='dense', ...) trains bit-identically
    to the nested atc/static construction (same seed, same metrics)."""
    with pytest.warns(DeprecationWarning):
        flat = MetaConfig(num_agents=K, tasks_per_agent=2, inner_lr=0.01,
                          mode="maml", combine="dense", topology="ring",
                          outer_optimizer="sgd", outer_lr=5e-3)
    sa, ma = _run_trainer(sine_model, flat, episodes)
    sb, mb = _run_trainer(sine_model, _nested("atc"), episodes)
    for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ma["loss"]),
                                  np.asarray(mb["loss"]))


def test_strategies_produce_distinct_iterates(sine_model, episodes):
    outs = {}
    for strategy in ["atc", "cta", "consensus", "none", "centralized"]:
        state, _ = _run_trainer(sine_model, _nested(strategy), episodes)
        outs[strategy] = np.concatenate(
            [np.asarray(x).ravel() for x in jax.tree.leaves(state.params)])
    names = list(outs)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            assert not np.array_equal(outs[a], outs[b]), (a, b)


def test_single_agent_degenerates_to_local(sine_model, episodes):
    mcfg = MetaConfig(num_agents=1, tasks_per_agent=2, inner_lr=0.01,
                      outer_optimizer="sgd", outer_lr=5e-3,
                      update_config=UpdateConfig(strategy="atc"),
                      topology_config=TopologyConfig(graph="ring"))
    one_ep = [(jax.tree.map(lambda x: x[:1], s),
               jax.tree.map(lambda x: x[:1], q)) for s, q in episodes]
    state, metrics = _run_trainer(sine_model, mcfg, one_ep)
    assert float(metrics["disagreement"]) == 0.0


# ---------------------------------------------------------------------------
# CommSchedule: skipped steps really skip the combine
# ---------------------------------------------------------------------------

def test_combine_every_skips_then_communicates(sine_model, episodes):
    """Before the first comm step the gated run is bit-identical to the
    non-cooperative baseline; on the comm step it diverges (communication
    happened), matching the atc composition applied at that step."""
    gated = _nested("atc", every=3)
    s_gated = init_state(jax.random.key(0), sine_model.init, gated,
                         identical_init=False)
    s_non = s_gated
    step_g = jax.jit(make_meta_step(sine_model.loss_fn, gated))
    step_n = jax.jit(make_meta_step(sine_model.loss_fn, _nested("none")))
    for i, (sup, qry) in enumerate(episodes[:3]):
        s_gated, _ = step_g(s_gated, sup, qry)
        s_non, _ = step_n(s_non, sup, qry)
        diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                   for a, b in zip(jax.tree.leaves(s_gated.params),
                                   jax.tree.leaves(s_non.params)))
        if i < 2:
            # distinct compiled programs: allow fusion-level float noise
            assert diff < 1e-8, f"step {i} should not communicate ({diff})"
        else:
            assert diff > 1e-6, "step 2 must run the combine"


def test_combine_every_hlo_has_no_unconditional_combine(sine_model):
    """Regression for the jnp.where path: with combine_every > 1 the K×K
    combine matmul must live only inside a conditional branch — the
    skipped-step execution path contains no contraction over the agent
    axis (and no collective).  The invariant itself lives in the
    conditional-comm lint rule (repro.analysis); this test binds it to a
    real lowered meta step."""
    from repro.analysis.rules import LintContext, run_rules

    mcfg = _nested("atc", every=2)
    step = make_meta_step(sine_model.loss_fn, mcfg)
    src = SineTaskSource(K=K, tasks_per_agent=2, shots=10, seed=0)
    ep = src.sample(0)
    sup = jax.tree.map(jnp.asarray, ep.support)
    qry = jax.tree.map(jnp.asarray, ep.query)
    state = init_state(jax.random.key(0), sine_model.init, mcfg)
    text = jax.jit(step).lower(state, sup, qry).compile().as_text()

    ctx = LintContext(hlo=text, K=K, combine_every=2)
    report = run_rules(ctx, only=["conditional-comm"])
    assert report.checked == ["conditional-comm"]
    assert report.ok, [f.message for f in report.findings]

    # the rule must not be vacuous here: the combine dot exists in this
    # module, so a gutted matcher would have tripped the no-markers branch
    assert f"f32[{K},{K}]" in text


# ---------------------------------------------------------------------------
# Stacked matrix schedules through the combine backends
# ---------------------------------------------------------------------------

def test_dense_combine_indexes_stacked_schedule():
    topo = topology.build_topology("ring", K)
    sched = topology.make_schedule("link_failure", topo, p=0.4, period=5,
                                   seed=3)
    stack = sched.stacked()
    assert stack.shape == (5, K, K)
    combine = diffusion.make_combine("dense", A=stack)
    phi = _phi(2)
    for step in [0, 2, 7]:                     # 7 wraps to 7 % 5 == 2
        out = combine(phi, jnp.int32(step))
        ref = diffusion.dense_combine(jnp.asarray(sched.matrix_at(step)), phi)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
    with pytest.raises(ValueError, match="step"):
        combine(phi)                           # stacked schedule needs step


def test_trainer_with_dynamic_schedules_contracts(sine_model, episodes):
    for schedule in ["link_failure", "gossip", "round_robin"]:
        mcfg = _nested("atc", schedule=schedule, link_failure_p=0.3)
        state, metrics = _run_trainer(sine_model, mcfg, episodes, steps=4)
        assert np.isfinite(float(metrics["loss"]))
        # any mixing schedule beats no mixing on disagreement
        s_non, m_non = _run_trainer(sine_model, _nested("none"), episodes,
                                    steps=4)
        assert (float(metrics["disagreement"])
                < float(m_non["disagreement"])), schedule


# ---------------------------------------------------------------------------
# Nested MetaConfig + deprecated flat aliases
# ---------------------------------------------------------------------------

def test_flat_fields_warn_and_map():
    with pytest.warns(DeprecationWarning, match="nested"):
        m = MetaConfig(num_agents=4, combine="centralized", topology="ring")
    assert m.update_config.strategy == "centralized"
    assert m.topology_config.graph == "ring"
    with pytest.warns(DeprecationWarning):
        m = MetaConfig(num_agents=4, combine="none")
    assert m.update_config.strategy == "none"
    with pytest.warns(DeprecationWarning):
        m = MetaConfig(num_agents=4, combine="sparse", mode="fomaml",
                       combine_every=4)
    assert m.update_config == UpdateConfig(strategy="atc", inner="fomaml",
                                           backend="sparse",
                                           combine_every=4)


def test_nested_config_is_silent_and_mirrors_flat():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        m = MetaConfig(num_agents=4,
                       update_config=UpdateConfig(strategy="cta",
                                                  inner="fomaml",
                                                  combine_every=2),
                       topology_config=TopologyConfig(graph="torus",
                                                      rule="uniform"))
    # legacy readers of the flat fields keep seeing the truth
    assert m.mode == "fomaml"
    assert m.topology == "torus"
    assert m.comb_rule == "uniform"
    assert m.combine_every == 2
    m2 = MetaConfig(update_config=UpdateConfig(strategy="none"),
                    topology_config=TopologyConfig())
    assert m2.combine == "none"


def test_defaults_construct_silently():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        MetaConfig(num_agents=3, outer_optimizer="adam")


def test_replace_on_flat_field_warns_about_conflict():
    """dataclasses.replace(cfg, mode=...) carries the nested configs over,
    so the flat value is discarded — loudly, not silently."""
    import dataclasses
    with pytest.warns(DeprecationWarning):
        cfg = MetaConfig(num_agents=4, mode="fomaml")
    # (a flat value equal to the field default is indistinguishable from
    # "not passed" and stays silent — only non-default conflicts can warn)
    with pytest.warns(DeprecationWarning, match="conflict"):
        cfg2 = dataclasses.replace(cfg, mode="reptile")
    assert cfg2.mode == "fomaml"        # nested configs won
    # replacing the nested config is the supported path: the value sticks.
    # A stale non-default flat mirror still triggers the conflict pointer
    # (replace() re-passes it), but the nested truth wins either way.
    with pytest.warns(DeprecationWarning, match="conflict"):
        cfg3 = dataclasses.replace(
            cfg, update_config=dataclasses.replace(cfg.update_config,
                                                   inner="maml"))
    assert cfg3.mode == "maml"
    # no stale mirror (flat at defaults) -> nested replace is silent
    base = MetaConfig(num_agents=4,
                      update_config=UpdateConfig(combine_every=1),
                      topology_config=TopologyConfig())
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg4 = dataclasses.replace(
            base, update_config=dataclasses.replace(base.update_config,
                                                    backend="pallas"))
    assert cfg4.combine == "pallas"


def test_topology_typo_rejected_even_at_k1():
    with pytest.raises(ValueError, match="unknown topology"):
        topology.combination_matrix(1, "rng")
    with pytest.raises(ValueError, match="unknown topology"):
        topology.build_topology("rng", 1)


def test_topology_mismatch_fails_early_with_both_numbers():
    mcfg = MetaConfig(num_agents=4,
                      update_config=UpdateConfig(strategy="atc"),
                      topology_config=TopologyConfig(graph="paper"))
    with pytest.raises(ValueError) as ei:
        make_meta_step(lambda p, b: jnp.zeros(()), mcfg)
    msg = str(ei.value)
    assert "paper" in msg and "4" in msg and "6" in msg


def test_helpers_resolve_nested_config():
    m = _nested("atc", graph="ring")
    assert topology_for(m).name == "ring"
    np.testing.assert_allclose(combination_matrix_for(m),
                               topology.combination_matrix(K, "ring"))
    assert schedule_for(m).static
