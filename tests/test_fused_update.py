"""Fused combine-then-update outer step: parity with the unfused chain.

The fused path (kernels/dif_combine.fused_combine_update driven by
core/fused.make_fused_outer) must reproduce the trainer's unfused
``clip → opt.update → strategy.apply/combine`` composition on arbitrary
ragged mixed-dtype pytrees, including every gating and schedule wrinkle:
``grad_clip=0.0`` (total clip), ``weight_decay > 0``, ``combine_every > 1``
(skipped comm steps still advance the moments), and stacked dynamic
schedules.  f32 leaves are held to near-exact tolerance; bf16 leaves get a
rounding-level budget — the fused path keeps the clipped gradient in fp32
for the moment update where the unfused chain rounds it to bf16 first.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MetaConfig, init_state, make_meta_step
from repro.core import diffusion, topology, update
from repro.core.fused import make_fused_outer, fused_unsupported_reason
from repro.core.meta_trainer import TopologyConfig, UpdateConfig
from repro.kernels.dif_combine.dif_combine import (dif_combine,
                                                   fused_combine_update)
from repro.optim import (adam, momentum, sgd, clip_by_global_norm,
                         get_optimizer)
from repro.optim.optimizers import Optimizer

K = 4


def ragged_params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(K, 7, 5)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(K, 3)), jnp.float32),
        "c": jnp.asarray(rng.normal(size=(K, 17)), jnp.bfloat16),
    }


def fake_grads(w, step):
    # deterministic, param- and step-dependent so moments actually move
    return jax.tree.map(
        lambda p: (p * 0.1 + 0.3 * (1 + step % 3)).astype(p.dtype), w)


def ring_table(stacked=False):
    topo = topology.build_topology("ring", K)
    if not stacked:
        return topo.matrix
    return topology.make_schedule("link_failure", topo, p=0.5, period=3,
                                  seed=1).stacked()


def unfused_run(opt, strategy, A, comm, grad_clip, params, steps):
    """Mirror of the trainer's unfused post-gradient block (meta_trainer
    make_meta_step): per-agent clip, opt.update, gated strategy apply."""
    An = np.asarray(A, np.float32) if A is not None else None
    st = opt.init(params)
    w = params
    for step in range(steps):
        grads = fake_grads(w, step)
        if grad_clip is not None:
            grads = jax.vmap(
                lambda g: clip_by_global_norm(g, grad_clip))(grads)
        upd, st = opt.update(grads, st, w)
        if strategy in ("none", "cta"):
            w = update.local_update(w, upd)
            continue
        gate = float(comm.is_comm_step(step))
        if strategy == "centralized":
            As = np.full((K, K), 1.0 / K, np.float32)
        else:
            As = An[step % An.shape[0]] if An.ndim == 3 else An
        Ae = gate * As + (1 - gate) * np.eye(K, dtype=np.float32)

        def mix(t):
            return jax.tree.map(
                lambda x: jnp.einsum(
                    "lk,lm->km", jnp.asarray(Ae),
                    x.astype(jnp.float32).reshape(K, -1)).reshape(x.shape),
                t)

        if strategy == "consensus":
            w = jax.tree.map(
                lambda m, u, p: (m + u.astype(jnp.float32)).astype(p.dtype),
                mix(w), upd, w)
        else:                                   # atc / centralized
            phi = jax.tree.map(
                lambda p, u: p.astype(jnp.float32) + u.astype(jnp.float32),
                w, upd)
            w = jax.tree.map(lambda m, p: m.astype(p.dtype), mix(phi), w)
    return w, st


def fused_run(opt, strategy, A, comm, grad_clip, params, steps):
    outer = make_fused_outer(opt, strategy, comm, A, grad_clip=grad_clip,
                             num_agents=K, interpret=True)
    st = opt.init(params)
    w = params
    for step in range(steps):
        w, st = outer(w, fake_grads(w, step), st, jnp.asarray(step))
    return w, st


def assert_tree_close(got, want, f32_tol=5e-6, bf16_tol=2e-2, like=None):
    """``like``: tree whose leaf dtypes pick the tolerance — fp32 moments
    of a bf16 param leaf still carry bf16-rounding deviation (the unfused
    chain rounds the clipped gradient to bf16 before the moment update)."""
    refs = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    dts = dict(jax.tree_util.tree_flatten_with_path(like or got)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        ref = refs[path]
        tol = bf16_tol if dts[path].dtype == jnp.bfloat16 else f32_tol
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err <= tol, f"{path}: err {err} > {tol} ({g.dtype})"


CASES = [
    # (name, opt, strategy, stacked, grad_clip, every)
    ("adam_atc_clip", lambda: adam(1e-2), "atc", False, 1.0, 1),
    ("adam_consensus_wd", lambda: adam(1e-2, weight_decay=1e-3),
     "consensus", False, None, 1),
    ("adam_atc_clip0", lambda: adam(1e-2), "atc", False, 0.0, 1),
    ("momentum_atc", lambda: momentum(1e-2, beta=0.8), "atc", False,
     None, 1),
    ("sgd_none", lambda: sgd(1e-2), "none", False, 0.5, 1),
    ("adam_centralized", lambda: adam(1e-2), "centralized", False,
     None, 1),
    ("adam_atc_every2", lambda: adam(1e-2), "atc", False, 1.0, 2),
    ("sgd_consensus_every3", lambda: sgd(1e-2), "consensus", False,
     None, 3),
    ("adam_atc_stacked", lambda: adam(1e-2), "atc", True, 1.0, 1),
    ("adam_atc_stacked_every2", lambda: adam(1e-2), "atc", True,
     None, 2),
]


@pytest.mark.parametrize("name,mk,strategy,stacked,clip,every",
                         CASES, ids=[c[0] for c in CASES])
def test_fused_matches_unfused(name, mk, strategy, stacked, clip, every):
    params = ragged_params()
    A = None if strategy in ("none",) else ring_table(stacked)
    comm = update.CommSchedule(every)
    w_f, st_f = fused_run(mk(), strategy, A, comm, clip, params, steps=5)
    w_u, st_u = unfused_run(mk(), strategy, A, comm, clip, params, steps=5)
    assert_tree_close(w_f, w_u)
    if hasattr(st_f, "mu"):
        assert int(st_f.step) == int(st_u.step) == 5
        assert_tree_close(st_f.mu, st_u.mu, like=params)
        assert_tree_close(st_f.nu, st_u.nu, like=params)
    elif hasattr(st_f, "velocity"):
        assert_tree_close(st_f.velocity, st_u.velocity, like=params)


def test_skipped_comm_steps_still_advance_moments():
    """combine_every=2: step 0 is a no-comm step (is_comm_step fires at
    every-1) — the mix must degenerate to identity while mu/nu move."""
    params = ragged_params()
    comm = update.CommSchedule(2)
    opt = adam(1e-2)
    outer = make_fused_outer(opt, "atc", comm, ring_table(), grad_clip=None,
                             num_agents=K, interpret=True)
    st0 = opt.init(params)
    w1, st1 = outer(params, fake_grads(params, 0), st0, jnp.asarray(0))
    assert int(st1.step) == 1
    assert float(jnp.max(jnp.abs(st1.mu["b"]))) > 0.0   # moments advanced
    # identity mix on the skipped step == plain local adam update
    w_ref, _ = unfused_run(adam(1e-2), "none", None, comm, None, params, 1)
    assert_tree_close(w1, w_ref)
    # ...and the next step does communicate: agents couple
    w2, _ = outer(w1, fake_grads(w1, 1), st1, jnp.asarray(1))
    w2_local, _ = outer(w1, fake_grads(w1, 1), st1, jnp.asarray(2))
    assert float(jnp.max(jnp.abs(w2["a"] - w2_local["a"]))) > 0.0


def test_total_clip_freezes_nothing_but_zeroes_direction():
    """grad_clip=0.0 zeroes every gradient: adam still bias-corrects a
    0/0 -> 0 direction (eps keeps it finite) so params only decay by wd."""
    params = ragged_params()
    comm = update.CommSchedule(1)
    opt = adam(1e-2)
    outer = make_fused_outer(opt, "none", comm, None, grad_clip=0.0,
                             num_agents=K, interpret=True)
    w1, st1 = outer(params, fake_grads(params, 0), opt.init(params),
                    jnp.asarray(0))
    assert_tree_close(w1, params, f32_tol=0.0, bf16_tol=0.0)
    assert float(jnp.max(jnp.abs(st1.mu["a"]))) == 0.0


def test_fused_backend_registered():
    """'fused' is a trainer mode, not a combine backend; the fused
    trainer's cta pre-mix (it runs before the gradient, so it cannot fuse
    with the update) is the pallas combine."""
    assert "fused" not in diffusion.combine_backends()
    A = ring_table()
    with pytest.raises(ValueError, match="registered"):
        diffusion.make_combine("fused", A=A)
    # 'auto' never volunteers the fused path — it changes optimizer wiring
    assert diffusion.select_backend(A) != "fused"
    assert diffusion.select_backend(ring_table(stacked=True)) != "fused"
    make_combine = diffusion.make_combine
    asked = []

    def spy(name, *args, **kw):
        asked.append(name)
        return make_combine(name, *args, **kw)

    mcfg = MetaConfig(num_agents=K, tasks_per_agent=2,
                      update_config=UpdateConfig(strategy="cta",
                                                 backend="fused"),
                      topology_config=TopologyConfig(graph="ring"))
    with mock.patch.object(diffusion, "make_combine", spy):
        make_meta_step(lambda params, batch: 0.0, mcfg)
    assert asked == ["pallas"]
    phi = ragged_params()
    got = make_combine("pallas", A=A, interpret=True)(phi, 0)
    want = make_combine("dense", A=A)(phi, 0)
    assert_tree_close(got, want)


def test_unqualified_optimizer_raises():
    bare = Optimizer(init=lambda p: (), update=lambda g, s, p: (g, s))
    assert fused_unsupported_reason(bare, "atc") is not None
    with pytest.raises(ValueError, match="FusedSpec"):
        make_fused_outer(bare, "atc", update.CommSchedule(1), ring_table())
    with pytest.raises(ValueError, match="no fused composition"):
        make_fused_outer(adam(1e-2), "mystery", update.CommSchedule(1),
                         ring_table())


def test_agent_count_mismatch_raises():
    with pytest.raises(ValueError, match="K=4.*num_agents=6"):
        make_fused_outer(adam(1e-2), "atc", update.CommSchedule(1),
                         ring_table(), num_agents=6)


def test_kernel_shape_errors_carry_both_numbers():
    w = jnp.zeros((K, 512), jnp.float32)
    g = jnp.zeros((K, 512), jnp.float32)
    tab = jnp.eye(K)[None]
    sel = jnp.zeros((1, 1), jnp.int32)
    ctl = jnp.asarray([[1.0, 1.0, 1.0]], jnp.float32)
    scale = jnp.ones((K, 1), jnp.float32)
    with pytest.raises(ValueError, match="100.*128"):
        fused_combine_update(tab, sel, ctl, scale, w, g, w, w, kind="adam",
                             lr=1e-2, block_m=100, interpret=True)
    with pytest.raises(ValueError, match=r"\(1, 4, 4\).*K=8"):
        fused_combine_update(tab, sel, ctl, jnp.ones((8, 1)),
                             jnp.zeros((8, 512)), jnp.zeros((8, 512)),
                             jnp.zeros((8, 512)), jnp.zeros((8, 512)),
                             kind="adam", lr=1e-2, interpret=True)
    with pytest.raises(ValueError, match="512.*384"):
        dif_combine(jnp.eye(K), w, block_m=384, interpret=True)


def test_meta_step_fused_matches_dense_end_to_end():
    """Full trainer assembly: make_meta_step(backend='fused') vs 'dense'
    on the paper's sine setting — same losses, params within tolerance."""
    from repro.configs import get_config
    from repro.data.sine import agent_sine_distributions, stacked_agent_batch
    from repro.models.simple import SineMLP

    model = SineMLP(get_config("sine_mlp"))

    def run(backend):
        mcfg = MetaConfig(
            num_agents=6, tasks_per_agent=2, inner_lr=0.01,
            outer_optimizer="adam", outer_lr=1e-3, grad_clip=1.0,
            update_config=UpdateConfig(strategy="atc", inner="maml",
                                       backend=backend, combine_every=2),
            topology_config=TopologyConfig(graph="paper"))
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=True)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))
        dists = agent_sine_distributions(6, seed=0)
        losses = []
        for _ in range(6):
            support, query = stacked_agent_batch(dists, 2, 10)
            state, metrics = step(state,
                                  jax.tree.map(jnp.asarray, support),
                                  jax.tree.map(jnp.asarray, query))
            losses.append(float(metrics["loss"]))
        return state, losses

    st_f, loss_f = run("fused")
    st_d, loss_d = run("dense")
    np.testing.assert_allclose(loss_f, loss_d, rtol=1e-4)
    assert_tree_close(st_f.params, st_d.params, f32_tol=1e-4)
    assert_tree_close(st_f.opt_state.mu, st_d.opt_state.mu, f32_tol=1e-4)
    assert int(st_f.opt_state.step) == int(st_d.opt_state.step) == 6


def test_meta_step_fused_rejects_custom_optimizer():
    from repro.configs import get_config
    from repro.models.simple import SineMLP

    model = SineMLP(get_config("sine_mlp"))
    mcfg = MetaConfig(
        num_agents=6, tasks_per_agent=2, inner_lr=0.01,
        update_config=UpdateConfig(strategy="atc", backend="fused"),
        topology_config=TopologyConfig(graph="paper"))
    bare = Optimizer(init=lambda p: (), update=lambda g, s, p: (g, s))
    with pytest.raises(ValueError, match="FusedSpec"):
        make_meta_step(model.loss_fn, mcfg, optimizer=bare)
