"""Decentralized meta-trainer: InnerAlgo × DiffusionStrategy × CommSchedule.

State layout: every parameter leaf carries a leading agent axis of size K.
One trainer step assembles three independently pluggable factors:

  1. **InnerAlgo** (``core/maml.py`` via the ``core/update.py`` registry):
     per-agent, per-task inner adaptation + meta-gradient (vmap over
     agents, vmap over tasks) — ``maml | fomaml | reptile``.
  2. **DiffusionStrategy** (``core/update.py``): how the per-agent outer
     update composes with the combine —
     ``atc | cta | consensus | none | centralized``.
  3. **CommSchedule** × **TopologySchedule**: *when* agents communicate
     (``combine_every``, gated by ``lax.cond`` so skipped steps move no
     bytes) and *over which graph* at each step
     (``static | link_failure | gossip | round_robin`` —
     ``core/topology.py``).

Strategy matrix — which combinations reproduce which baseline:

  =============  ==========  ============  ==============================
  strategy       inner       schedule      reproduces
  =============  ==========  ============  ==============================
  atc            maml        static        Dif-MAML (paper Algorithm 1)
  none           maml        --            non-cooperative baseline
                                           (paper Fig. 2b/3, A = I)
  centralized    maml        --            centralized MAML reference
                                           (paper Fig. 2b/3; equals the
                                           full-graph uniform A exactly)
  atc            fomaml      static        first-order Dif-MAML (Nichol
                                           et al. 2018 inner algo)
  cta            maml        static        combine-then-adapt diffusion
                                           (Sayed 2014; gradient at the
                                           mixed iterate)
  consensus      maml        static        consensus/DGD composition
                                           (gradient at own iterate)
  atc            maml        link_failure  Dif-MAML under i.i.d. edge
                                           drops (beyond-paper)
  atc            maml        gossip        randomized pairwise gossip
                                           (Boyd et al. 2006 flavor)
  =============  ==========  ============  ==============================

Configuration is nested: :class:`TopologyConfig` (who/when graph-wise) and
:class:`UpdateConfig` (strategy/inner/backend/cadence) inside
:class:`MetaConfig`.  The legacy flat fields (``mode``, ``combine``,
``topology``, ``comb_rule``, ``combine_every``) still construct and train
but are deprecated aliases — they emit a ``DeprecationWarning`` pointing at
the nested configs, and the nested configs win when both are given.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diffusion, maml, topology, update
from repro.optim import Optimizer, clip_by_global_norm, get_optimizer

PyTree = Any
LossFn = Callable[[PyTree, Any], jax.Array]

__all__ = ["TopologyConfig", "UpdateConfig", "MetaConfig", "TrainState",
           "init_state", "make_meta_step", "make_eval_fn",
           "topology_for", "schedule_for", "combination_matrix_for",
           "strategy_for_combine"]


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Who mixes with whom: the graph family, the weight rule, and the
    per-step schedule (:data:`repro.core.topology.SCHEDULES`)."""

    graph: str = "paper"              # ring | grid | torus | full | star | erdos | paper
    rule: str = "metropolis"          # metropolis | uniform
    schedule: str = "static"          # static | link_failure | gossip | round_robin
    link_failure_p: float = 0.2       # per-edge i.i.d. drop prob (link_failure)
    period: int = 64                  # pre-sampled steps for random schedules
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class UpdateConfig:
    """How and when the outer update composes with communication."""

    strategy: str = "atc"             # update.update_strategies() name
    inner: str = "maml"               # update.inner_algos() name
    backend: str = "dense"            # diffusion.make_combine request | 'fused'
    combine_every: int = 1            # CommSchedule cadence


# Deprecated flat aliases and the defaults that detect explicit use.
_FLAT_DEFAULTS = {"mode": "maml", "combine": "dense", "topology": "paper",
                  "comb_rule": "metropolis", "combine_every": 1}


def _mirror(tc: "TopologyConfig", uc: "UpdateConfig") -> dict:
    """The flat-alias values implied by the nested configs — what legacy
    readers of ``mode``/``combine``/... see."""
    return {
        "mode": uc.inner,
        "combine": (uc.strategy if uc.strategy in ("none", "centralized")
                    else uc.backend),
        "topology": tc.graph,
        "comb_rule": tc.rule,
        "combine_every": uc.combine_every,
    }


def strategy_for_combine(combine: str, default: str = "atc") -> str:
    """Map a legacy flat ``combine`` name to the strategy it implied:
    'none'/'centralized' were strategies masquerading as backends; every
    real backend name meant plain ATC.  The single owner of this mapping —
    MetaConfig's alias resolution and launch's ``--combine`` override both
    route through here."""
    return {"none": "none", "centralized": "centralized"}.get(combine,
                                                              default)


@dataclasses.dataclass(frozen=True)
class MetaConfig:
    num_agents: int = 6
    tasks_per_agent: int = 4          # |S_k|
    inner_lr: float = 0.01            # α
    inner_steps: int = 1
    outer_optimizer: str = "adam"
    outer_lr: float = 1e-3            # μ
    grad_clip: float | None = None
    hvp_subsample: float = 1.0        # curvature-term batch fraction (beyond-paper)

    # -- the composition axes (preferred surface) ---------------------------
    topology_config: TopologyConfig | None = None
    update_config: UpdateConfig | None = None

    # -- deprecated flat aliases (kept so existing call sites construct) ----
    mode: str = "maml"                # -> update_config.inner
    combine: str = "dense"            # -> update_config.{strategy,backend}
    topology: str = "paper"           # -> topology_config.graph
    comb_rule: str = "metropolis"     # -> topology_config.rule
    combine_every: int = 1            # -> update_config.combine_every

    def __post_init__(self):
        tc, uc = self.topology_config, self.update_config
        if tc is None or uc is None:
            used = [f for f in _FLAT_DEFAULTS
                    if getattr(self, f) != _FLAT_DEFAULTS[f]]
            if used:
                warnings.warn(
                    f"MetaConfig flat field(s) {used} are deprecated "
                    f"aliases; build the nested configs instead — "
                    f"MetaConfig(update_config=UpdateConfig(strategy=..., "
                    f"inner=..., backend=..., combine_every=...), "
                    f"topology_config=TopologyConfig(graph=..., rule=..., "
                    f"schedule=...))",
                    DeprecationWarning, stacklevel=3)
            if uc is None:
                uc = UpdateConfig(strategy=strategy_for_combine(self.combine),
                                  inner=self.mode,
                                  backend=self.combine,
                                  combine_every=self.combine_every)
            if tc is None:
                tc = TopologyConfig(graph=self.topology, rule=self.comb_rule)
            object.__setattr__(self, "topology_config", tc)
            object.__setattr__(self, "update_config", uc)
        else:
            # Both nested configs present (direct nested construction, or a
            # dataclasses.replace carrying them over): the nested configs
            # are the source of truth, so any flat value disagreeing with
            # their mirror is about to be discarded — e.g.
            # ``dataclasses.replace(cfg, mode='fomaml')`` on a config whose
            # nested update_config still says 'maml'.  Silent discard broke
            # the flat-alias contract, so say it out loud.
            ignored = [f for f in _FLAT_DEFAULTS
                       if getattr(self, f) != _mirror(tc, uc)[f]
                       and getattr(self, f) != _FLAT_DEFAULTS[f]]
            if ignored:
                warnings.warn(
                    f"MetaConfig flat field(s) {ignored} conflict with the "
                    f"nested topology_config/update_config and are ignored "
                    f"(the nested configs win). To change these via "
                    f"dataclasses.replace, replace the nested config, e.g. "
                    f"replace(cfg, update_config=dataclasses.replace("
                    f"cfg.update_config, inner=...))",
                    DeprecationWarning, stacklevel=3)
        # Mirror nested -> flat so legacy readers keep seeing the truth.
        for field, value in _mirror(tc, uc).items():
            object.__setattr__(self, field, value)


class TrainState(NamedTuple):
    step: jax.Array
    params: PyTree       # leading agent axis K on every leaf
    opt_state: PyTree    # per-agent moments (same leading axis)


def topology_for(cfg: MetaConfig) -> topology.Topology:
    """The validated :class:`~repro.core.topology.Topology` instance —
    fixed-size graphs (``paper``) reject a mismatched ``num_agents`` here
    with both numbers, before any array work."""
    tc = cfg.topology_config
    return topology.build_topology(tc.graph, cfg.num_agents, tc.rule)


def schedule_for(cfg: MetaConfig) -> topology.TopologySchedule:
    """The per-step combination-matrix schedule the trainer runs on."""
    tc = cfg.topology_config
    kw = {}
    if tc.schedule == "link_failure":
        kw = dict(p=tc.link_failure_p, period=tc.period, seed=tc.seed)
    elif tc.schedule == "gossip":
        kw = dict(period=tc.period, seed=tc.seed)
    return topology.make_schedule(tc.schedule, topology_for(cfg), **kw)


def combination_matrix_for(cfg: MetaConfig) -> np.ndarray:
    """The static ``(K, K)`` matrix (schedule-independent legacy surface)."""
    if cfg.num_agents == 1:
        return np.ones((1, 1))
    return topology_for(cfg).matrix


def init_state(
    rng: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    cfg: MetaConfig,
    optimizer: Optimizer | None = None,
    identical_init: bool = False,
) -> TrainState:
    """Stack K independently-initialized launch models (paper: "Initialize
    the launch models {w_{k,0}}")."""
    opt = optimizer or get_optimizer(cfg.outer_optimizer, cfg.outer_lr)
    if identical_init:
        p0 = init_fn(rng)
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.num_agents,) + x.shape), p0)
    else:
        keys = jax.random.split(rng, cfg.num_agents)
        params = jax.vmap(init_fn)(keys)
    opt_state = opt.init(params)
    return TrainState(jnp.zeros((), jnp.int32), params, opt_state)


def make_meta_step(
    loss_fn: LossFn,
    cfg: MetaConfig,
    optimizer: Optimizer | None = None,
    A: np.ndarray | None = None,
    combine_fn: diffusion.CombineFn | None = None,
    freeze_mask: PyTree | None = None,
    disagreement_fn: Callable[[PyTree], jax.Array] | None = None,
):
    """Returns ``step(state, support, query) -> (state, metrics)``:
    the InnerAlgo × DiffusionStrategy × CommSchedule assembly.

    ``support``/``query``: pytrees of arrays with leading axes
    ``(K, tasks_per_agent, task_batch, ...)``.

    ``A`` may be one ``(K, K)`` matrix or a stacked ``(S, K, K)`` schedule;
    when omitted it is derived from ``cfg.topology_config`` via
    :func:`schedule_for`.  ``combine_fn`` overrides the combine — mesh-aware
    backends need the leaf PartitionSpecs only the launch layer knows, so
    launch/steps.py builds them via ``diffusion.make_combine`` and injects
    them here (signature ``combine(phi, step)``).  ``disagreement_fn``
    likewise overrides the ``disagreement`` metric (default
    :func:`diffusion.disagreement`); launch/steps.py injects
    :func:`diffusion.make_mesh_disagreement` on agent meshes.

    With ``combine_every > 1`` the communication is gated by ``lax.cond``:
    skipped steps execute no combine matmul/collective at all (the old
    ``jnp.where`` path ran the full combine every step and discarded it).
    """
    opt = optimizer or get_optimizer(cfg.outer_optimizer, cfg.outer_lr)
    uc = cfg.update_config
    strategy_name = uc.strategy if cfg.num_agents > 1 else "none"
    strategy = update.get_strategy(strategy_name)
    algo = update.get_inner_algo(uc.inner)
    comm = update.CommSchedule(uc.combine_every)
    fused_outer = None
    if uc.backend == "fused":
        # one-pass combine-then-update: clip scale, moments, launch-model
        # mix all happen inside a single kernel sweep over the param bytes
        from repro.core.fused import make_fused_outer
        if A is None and strategy.needs_combine_fn:
            A = schedule_for(cfg).stacked()
        fused_outer = make_fused_outer(
            opt, strategy_name, comm, A, grad_clip=cfg.grad_clip,
            num_agents=cfg.num_agents)
    if (combine_fn is None and strategy.needs_combine_fn
            and (fused_outer is None or strategy.pre_combine)):
        if A is None:
            A = schedule_for(cfg).stacked()
        # the fused outer step mixes inside its kernel; the cta pre-mix
        # runs before the gradient, so it takes the one-pass pallas combine
        combine_fn = diffusion.make_combine(
            "pallas" if fused_outer is not None else uc.backend, A=A)
    if combine_fn is not None:
        # one name for the diffusion combine, whichever backend made it
        combine_fn = jax.named_scope("dif.step.combine")(combine_fn)
    disagreement_fn = jax.named_scope("dif.step.disagreement")(
        disagreement_fn or diffusion.disagreement)

    def per_agent(params_k, support_k, query_k):
        return maml.multi_task_meta_grad(
            loss_fn, params_k, support_k, query_k,
            alpha=cfg.inner_lr, steps=cfg.inner_steps, mode=algo.mode,
            hvp_subsample=cfg.hvp_subsample, freeze_mask=freeze_mask)

    # lax.cond gating only matters when the strategy actually communicates
    gated = strategy.communicates and not comm.always

    def step(state: TrainState, support: Any, query: Any):
        idx = state.step
        base = state.params
        if strategy.pre_combine:
            mix = lambda p: combine_fn(p, idx)
            base = (jax.lax.cond(comm.is_comm_step(idx), mix, lambda p: p,
                                 base)
                    if gated else mix(base))
        losses, grads = jax.vmap(per_agent)(base, support, query)
        with jax.named_scope("dif.step.outer_update"):
            if fused_outer is not None:
                # no lax.cond: skipped comm steps must still advance the
                # moments, and the kernel's gate blends the mix to identity
                params, opt_state = fused_outer(base, grads, state.opt_state,
                                                idx)
            else:
                if cfg.grad_clip is not None:   # 0.0 is a valid (total) clip
                    grads = jax.vmap(lambda g: clip_by_global_norm(
                        g, cfg.grad_clip))(grads)
                updates, opt_state = opt.update(grads, state.opt_state, base)
                if gated and not strategy.pre_combine:
                    params = jax.lax.cond(
                        comm.is_comm_step(idx),
                        lambda p, u: strategy.apply(p, u, combine_fn, idx),
                        update.local_update, base, updates)
                else:
                    params = strategy.apply(base, updates, combine_fn, idx)
        metrics = {
            "loss": jnp.mean(losses),
            "per_agent_loss": losses,
            "disagreement": disagreement_fn(params),
        }
        return TrainState(state.step + 1, params, opt_state), metrics

    return step


def make_eval_fn(loss_fn: LossFn, inner_lr: float, inner_steps: int = 1):
    """Compatibility wrapper over :class:`repro.eval.EvalHarness`.

    Returns ``evaluate(params, support, query) -> (tasks, steps+1)``:
    adapt one launch model on each eval task's support set and report the
    query loss after *each* inner step (index 0 = zero-shot), exactly
    :meth:`EvalHarness.curves`.  New code should build the harness
    directly — it adds the recurring-vs-unseen split protocol, per-agent
    curves, and the generalization-gap report."""
    from repro.eval.harness import EvalHarness
    return EvalHarness(loss_fn, inner_lr=inner_lr,
                       inner_steps=inner_steps).curves
