"""Builders: per-(architecture × input-shape × mesh) train/serve steps with
full sharding trees and ShapeDtypeStruct input specs — shared by the
dry-run, the trainer, and the server.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape, resolve_input_shape
from repro.core import (MetaConfig, TopologyConfig, UpdateConfig, diffusion,
                        update)
from repro.core.meta_trainer import (TrainState, make_meta_step, schedule_for,
                                     strategy_for_combine)
from repro.models.init import Spec, abstract, axes_tree, with_agent_axis
from repro.models.transformer import build_model
from repro.optim import get_optimizer
from repro.sharding.rules import rules_for, spec_for, tree_shardings

PyTree = Any

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# ---------------------------------------------------------------------------
# Agent / batch geometry
# ---------------------------------------------------------------------------

def agent_count(cfg: ArchConfig, mesh: Mesh, agents: int | None = None
                ) -> int:
    """K for an arch on a mesh.

    ``agents`` is the caller's K and is returned unchanged once the mesh can
    hold it: a first-class ``agent`` axis must have extent K, and on legacy
    meshes the axes the agents tile (``pod``/``data`` per
    ``cfg.placement``) must divide K — several agents then stack on the
    leading ``agent`` dimension of each device.  Anything else raises with
    both numbers.  Without ``agents``, K is read off the mesh: the ``agent``
    axis, or on legacy meshes one agent per pod, or agents tiling the full
    data-parallel extent."""
    from repro.sharding.rules import _axis_sizes
    sizes = _axis_sizes(mesh)
    if "agent" in sizes:
        tiled = sizes["agent"]
    elif cfg.placement == "pod":
        tiled = sizes.get("pod", 1)
    else:
        tiled = sizes.get("data", 1) * sizes.get("pod", 1)
    if agents is None:
        return tiled
    if agents < 1 or (agents != tiled if "agent" in sizes
                      else agents % tiled):
        raise ValueError(
            f"K={agents} agents do not fit mesh {dict(sizes)}: "
            + ("the agent axis must have extent K" if "agent" in sizes
               else f"the {tiled} agent slice(s) of the mesh must divide K"))
    return agents


def batch_geometry(cfg: ArchConfig, shape: InputShape, K: int
                   ) -> tuple[int, int]:
    """(tasks_per_agent, task_batch): B = K · T · tb · 2 (support+query).

    T starts at ``cfg.meta_tasks`` and falls back toward 1 until it divides
    the per-agent half-batch; the global batch itself must factor exactly —
    a remainder would silently vanish in the (K, T, 2·tb) fold."""
    B = shape.global_batch
    if K < 1 or B < 2 * K or B % (2 * K):
        raise ValueError(
            f"global_batch={B} cannot be split across K={K} agents: the "
            f"meta step folds the batch as B = K·T·tb·2 (support+query), "
            f"so global_batch must be a multiple of 2·K = {2 * max(K, 1)} "
            f"(minimum {2 * max(K, 1)})")
    half = B // K // 2
    T = cfg.meta_tasks
    while half % T:
        T -= 1
    if T != cfg.meta_tasks:
        import warnings
        warnings.warn(
            f"meta_tasks={cfg.meta_tasks} does not divide the per-agent "
            f"half-batch {half} (global_batch={B}, K={K}); falling back to "
            f"T={T} tasks per agent — the eq. 4 multi-task average degrades "
            f"(T=1 erases it entirely). Pick a global_batch divisible by "
            f"2·K·meta_tasks to keep the requested T.",
            RuntimeWarning, stacklevel=2)
    return T, half // T


def modality_extras(cfg: ArchConfig, lead: tuple[int, ...], dt) -> dict:
    """Zero-stub modality inputs (audio frames / vision patches) the model's
    loss expects beyond tokens/labels, with the given leading axes — the ONE
    place the modality-input contract is spelled; train pipeline
    (``lead=(B,)``), eval harness (``lead=(n_tasks, tb)``) and serve all
    build their stubs here."""
    extras = {}
    if cfg.arch_type == "audio":
        extras["encoder_frames"] = jnp.zeros(
            lead + (cfg.encoder_frames, cfg.d_model), dt)
    if cfg.arch_type == "vlm":
        extras["image_patches"] = jnp.zeros(
            lead + (cfg.num_patches, cfg.d_model), dt)
    return extras


def split_meta_batch(cfg: ArchConfig, batch: dict, K: int, T: int, tb: int,
                     fold_spec: P | None = None, mesh: Mesh | None = None
                     ) -> tuple[dict, dict]:
    """(B, ...) arrays → support/query dicts with leading (K, T, tb, ...).

    ``fold_spec`` re-asserts the sharding of the folded layout — XLA's
    sharding propagation cannot split a dim-0 sharding across the
    non-adjacent (agent, task-batch) factors of the reshape, and silently
    replicates the batch without this constraint (measured: ~16× per-device
    FLOPs on pod-placement archs)."""

    def leaf(x):
        rest = x.shape[1:]
        out = x.reshape((K, T, 2 * tb) + rest)
        if fold_spec is not None and mesh is not None:
            spec = P(*(tuple(fold_spec) + (None,) * len(rest)))
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, spec))
        return out

    folded = {k: leaf(v) for k, v in batch.items()}
    support = {k: v[:, :, :tb] for k, v in folded.items()}
    query = {k: v[:, :, tb:] for k, v in folded.items()}
    return support, query


# ---------------------------------------------------------------------------
# Input specs (deliverable f): ShapeDtypeStructs for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape_name: str | InputShape
                ) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one (arch × input-shape).  The shape
    may be a registry name or a bare :class:`InputShape` (one-shot
    geometries need not touch the global registry).

    train/prefill: {tokens, labels [, encoder_frames | image_patches]}
    decode:        {token, pos, cache}
    """
    shape = resolve_input_shape(shape_name)
    dt = DTYPES[cfg.dtype]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        specs: dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if cfg.arch_type == "audio":
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_frames, cfg.d_model), dt)
        if cfg.arch_type == "vlm":
            specs["image_patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), dt)
        return specs
    # decode: one new token against a seq_len cache
    model = build_model(cfg)
    cache = abstract(model.cache_specs(B, S), dt)
    return {
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        "cache": cache,
    }


def input_axes(cfg: ArchConfig, shape_name: str | InputShape
               ) -> dict[str, Any]:
    """Logical axes matching input_specs (for sharding assignment)."""
    shape = resolve_input_shape(shape_name)
    if shape.kind in ("train", "prefill"):
        axes: dict[str, Any] = {
            "tokens": ("batch", None),
            "labels": ("batch", None),
        }
        if cfg.arch_type == "audio":
            axes["encoder_frames"] = ("batch", None, "embed")
        if cfg.arch_type == "vlm":
            axes["image_patches"] = ("batch", None, "embed")
        return axes
    model = build_model(cfg)
    cache_axes = axes_tree(model.cache_specs(shape.global_batch, shape.seq_len))
    return {"token": ("batch", None), "pos": ("batch",), "cache": cache_axes}


# ---------------------------------------------------------------------------
# Train step (Dif-MAML meta-iteration)
# ---------------------------------------------------------------------------

def meta_config_for(cfg: ArchConfig, K: int, T: int, *,
                    strategy: str | None = None,
                    schedule: str = "static",
                    link_failure_p: float = 0.2,
                    schedule_seed: int = 0) -> MetaConfig:
    """Assemble the nested MetaConfig from the arch's meta fields plus the
    run's strategy/schedule choices (``--strategy``/``--topology-schedule``
    in launch/train.py)."""
    if K == 1:
        strategy, backend = "none", "none"
    else:
        strategy, backend = strategy or "atc", cfg.combine
    return MetaConfig(
        num_agents=K,
        tasks_per_agent=T,
        inner_lr=cfg.inner_lr,
        inner_steps=cfg.inner_steps,
        outer_optimizer=cfg.outer_optimizer,
        outer_lr=cfg.outer_lr,
        hvp_subsample=cfg.hvp_subsample,
        update_config=UpdateConfig(strategy=strategy, inner=cfg.meta_mode,
                                   backend=backend),
        topology_config=TopologyConfig(graph=cfg.topology,
                                       schedule=schedule,
                                       link_failure_p=link_failure_p,
                                       seed=schedule_seed),
    )


@dataclasses.dataclass
class TrainBundle:
    cfg: ArchConfig
    mesh: Mesh
    K: int
    T: int
    tb: int
    step_fn: Any                  # (state, batch) -> (state, metrics)
    state_specs: Any              # abstract TrainState
    state_shardings: Any
    batch_shardings: Any
    init_state: Any               # () -> TrainState (materialized)
    loss_fn: Any = None           # (params, batch) -> scalar (single agent)
    mcfg: Any = None              # the assembled MetaConfig
    schedule: Any = None          # TopologySchedule (None when K == 1)
    outer_dtype: str = ""         # resolved params/grads storage dtype
    combine_dtype: str = ""       # resolved combine wire format
    combine_backend: str = ""     # diffusion.select_backend's resolution
    # how the disagreement metric reduces over agents: 'stacked' (GSPMD's
    # agent mean) or 'mesh_all_to_all' (diffusion.make_mesh_disagreement)
    disagreement_backend: str = "stacked"

    def make_eval_harness(self, inner_steps: int | None = None):
        """The in-training recurring-vs-unseen eval engine, bound to this
        bundle's model loss and inner learning rate — the same
        ``maml.inner_adapt`` path the meta step differentiates through."""
        from repro.eval.harness import EvalHarness
        return EvalHarness(
            self.loss_fn, inner_lr=self.cfg.inner_lr,
            inner_steps=self.cfg.inner_steps if inner_steps is None
            else inner_steps)

    def eval_prepare(self):
        """``prepare`` hook for :meth:`EvalHarness.evaluate`: appends the
        per-task modality stubs (``modality_extras``) the model's loss
        expects, on the task-leading eval layout."""
        cfg, dt = self.cfg, DTYPES[self.cfg.dtype]

        def add(d):
            extras = modality_extras(cfg, d["tokens"].shape[:2], dt)
            return {**d, **extras} if extras else d

        return lambda sq: (add(sq[0]), add(sq[1]))

    def make_pipeline(self, source, *, depth: int = 2, start_step: int = 0,
                      stack: int | None = None):
        """Wrap a ``TaskSource`` bound to this bundle's (K, T, tb) geometry
        in a :class:`~repro.data.pipeline.MetaBatchPipeline` yielding
        device-ready global batches: the episode is flattened to the
        ``(B, ...)`` layout ``step_fn`` folds back with
        ``split_meta_batch``, modality stubs are appended, and the batch is
        ``device_put`` onto ``batch_shardings`` on the prefetch thread —
        host-side sampling and H2D overlap the jitted step.

        ``stack=C`` feeds the superstep driver: each ``next()`` yields C
        consecutive meta-batches stacked on a new leading dispatch axis of
        size C (one host assembly + one ``device_put`` per dispatch), the
        layout :func:`make_superstep`'s ``lax.scan`` unstacks on device —
        C=1 still carries the (1, B, ...) axis so one driver serves every
        C.  ``stack=None`` (default) keeps the legacy per-step ``(B, ...)``
        layout for direct ``step_fn`` consumers.  The sample sequence is
        identical either way."""
        from repro.data.pipeline import MetaBatchPipeline
        src_tb = getattr(source, "task_batch", self.tb)
        if (source.K, source.tasks_per_agent, src_tb) != (self.K, self.T,
                                                          self.tb):
            raise ValueError(
                f"source geometry (K={source.K}, T={source.tasks_per_agent}, "
                f"tb={src_tb}) does not match the bundle's (K={self.K}, "
                f"T={self.T}, tb={self.tb})")
        cfg, dt = self.cfg, DTYPES[self.cfg.dtype]
        B = self.K * self.T * self.tb * 2

        if stack is None:
            extras = modality_extras(cfg, (B,), dt)

            def prepare(ep):
                batch = ep.as_flat_batch()
                batch.update(extras)
                return jax.device_put(
                    batch, {k: self.batch_shardings[k] for k in batch})
        else:
            if stack < 1:
                raise ValueError(f"stack must be >= 1, got {stack}")
            extras = modality_extras(cfg, (stack, B), dt)
            # the stacked leading (dispatch) axis is unsharded; every batch
            # dim keeps its per-step spec one position to the right
            stacked_sh = {
                k: NamedSharding(self.mesh, P(*((None,) + tuple(sh.spec))))
                for k, sh in self.batch_shardings.items()}

            def prepare(eps):
                eps = eps if isinstance(eps, list) else [eps]
                flat = [ep.as_flat_batch() for ep in eps]
                batch = {k: np.stack([b[k] for b in flat]) for k in flat[0]}
                batch.update(extras)
                return jax.device_put(
                    batch, {k: stacked_sh[k] for k in batch})

        return MetaBatchPipeline(source, depth=depth, prepare=prepare,
                                 start_step=start_step,
                                 stack=1 if stack is None else stack)

    def lint_metadata(self) -> dict:
        """The facts the compiled-program lint rules (``repro.analysis``)
        need about this bundle's train step: mesh geometry, the combine's
        schedule degree and per-device wire-shard size, backend wire
        metadata, and the donated-leaf count — derived here, in the one
        place that owns the bundle's sharding and combine resolution."""
        from repro.compat import mesh_axis_sizes
        from repro.launch.hlo_cost import tree_shard_bytes
        sizes = mesh_axis_sizes(self.mesh)
        deg = self.schedule.ir().degree if self.schedule is not None else 0
        shard = tree_shard_bytes(
            self.state_shardings.params, self.state_specs.params, sizes,
            elem_bytes=diffusion.wire_elem_bytes(self.combine_dtype))
        backend = self.combine_backend or "none"
        try:
            bmeta = diffusion.backend_lint_metadata(backend,
                                                    self.combine_dtype)
        except ValueError:
            bmeta = {"backend": backend, "emits_permutes": False,
                     "wire_hlo_dtype": "f32"}
        ucfg = self.mcfg.update_config if self.mcfg is not None else None
        return {
            "n_dev": int(np.prod(self.mesh.devices.shape)),
            "mesh_axes": dict(sizes),
            "K": self.K,
            "degree": int(deg),
            "shard_bytes": int(shard),
            "wire_dtype": self.combine_dtype,
            "combine_every": int(getattr(ucfg, "combine_every", 1) or 1),
            "expected_aliases": len(jax.tree.leaves(self.state_specs)),
            **bmeta,
        }


def opt_state_axes(opt_name: str, params_axes: PyTree) -> PyTree:
    from repro.optim.optimizers import AdamState, MomentumState
    if opt_name in ("adam", "adamw"):
        return AdamState((), params_axes, params_axes)
    if opt_name == "momentum":
        return MomentumState(params_axes)
    return ()


def build_train(cfg: ArchConfig, mesh: Mesh,
                shape_name: str | InputShape = "train_4k",
                combine_override: str | None = None, *,
                strategy: str | None = None,
                schedule: str = "static",
                link_failure_p: float = 0.2,
                schedule_seed: int = 0,
                agents: int | None = None) -> TrainBundle:
    """Everything the trainer needs for one (arch × shape × mesh) meta step.
    ``agents`` fixes K (see :func:`agent_count`); without it K is read off
    the mesh."""
    shape = resolve_input_shape(shape_name)
    assert shape.kind in ("train", "prefill")
    dt = DTYPES[cfg.dtype]
    # Outer-loop storage: params/grads live in out_dt; Adam moments stay
    # fp32 regardless (adam.init allocates f32, updates come back in
    # p.dtype).  Activations/inputs keep cfg.dtype.
    outer_dtype = cfg.outer_dtype or cfg.dtype
    out_dt = DTYPES[outer_dtype]
    wire_dtype = diffusion.resolve_combine_dtype(outer_dtype,
                                                 cfg.combine_dtype or None)
    model = build_model(cfg)
    agent_mesh = "agent" in mesh.axis_names
    intra_agent_data = "data" in mesh.axis_names and (
        agent_mesh or cfg.placement == "pod")
    if intra_agent_data:
        # keep per-task activations batch-sharded over the data axis (the
        # agent/task dims are vmapped away above this constraint)
        model.act_sharding = NamedSharding(mesh, P("data", None, None))
    K = agent_count(cfg, mesh, agents)
    T, tb = batch_geometry(cfg, shape, K)
    mcfg = meta_config_for(cfg, K, T, strategy=strategy, schedule=schedule,
                           link_failure_p=link_failure_p,
                           schedule_seed=schedule_seed)
    if combine_override:
        # a bare 'none'/'centralized' override keeps the legacy meaning of
        # selecting that *strategy* (unless one was requested explicitly)
        uc = mcfg.update_config
        strat = (uc.strategy if strategy
                 else strategy_for_combine(combine_override,
                                           default=uc.strategy))
        mcfg = dataclasses.replace(mcfg, update_config=dataclasses.replace(
            uc, strategy=strat, backend=combine_override))
    opt = get_optimizer(cfg.outer_optimizer, cfg.outer_lr)
    sched = schedule_for(mcfg) if K > 1 else None
    A = sched.stacked() if sched is not None else np.ones((1, 1))

    # ---- shardings (needed below for the sparse combine's in_specs) -------
    rules = rules_for(cfg, mesh, kind="train")
    p_specs = with_agent_axis(model.specs(), K)
    p_axes = axes_tree(p_specs)
    p_abs = abstract(p_specs, out_dt)
    params_sh = tree_shardings(p_axes, p_abs, rules, mesh)

    multi_pod = "pod" in mesh.axis_names
    if agent_mesh:
        agent_axis = "agent"
    elif cfg.placement == "pod" and multi_pod:
        agent_axis = "pod"
    else:
        agent_axis = "data"
    strat_obj = update.get_strategy(
        mcfg.update_config.strategy if K > 1 else "none")
    backend = mcfg.update_config.backend
    combine_fn = None
    disagreement_fn, disagreement_backend = None, "stacked"
    if backend == "fused":
        # One-pass combine-then-update: make_meta_step builds the fused
        # outer from mcfg (it owns optimizer/strategy/comm wiring); no
        # combine_fn is injected — the replicated (K, m) kernel layout has
        # no shard_map exchange, so a first-class agent mesh must keep the
        # ppermute backends.
        if agent_mesh:
            raise ValueError(
                "backend='fused' runs the packed single-host kernel layout "
                "and cannot serve a mesh with a first-class agent axis "
                f"(mesh axes {mesh.axis_names}); use 'sparse' there, or a "
                "host mesh for the fused outer step.")
    elif strat_obj.needs_combine_fn and K > 1:
        param_specs = jax.tree.map(lambda s: s.spec, params_sh)
        combine_fn = diffusion.make_combine(
            backend, A=A, axis_name=agent_axis, mesh=mesh,
            in_specs=param_specs, combine_dtype=wire_dtype)
        # the name the lint layer and TrainBundle see is the backend
        # make_combine lowered
        backend = diffusion.select_backend(
            A, mesh=mesh, axis_name=agent_axis, request=backend)
        if agent_mesh and backend == "mesh_sparse_dynamic":
            # one agent a shard: the metric's agent mean rides one
            # all-to-all instead of GSPMD's f32 all-reduce of every leaf
            disagreement_fn = diffusion.make_mesh_disagreement(
                mesh, agent_axis, param_specs)
            disagreement_backend = "mesh_all_to_all"
    else:
        # no combine is injected at all (K=1 / strategies without one)
        backend = "none"
    freeze_mask = None
    if cfg.inner_freeze:
        # ANIL-style: the named subtree (e.g. 'encoder') is frozen in the
        # inner loop — its inner gradient, update, and curvature cross-terms
        # vanish; the outer step still trains it (EXPERIMENTS HC3).
        freeze_mask = jax.tree_util.tree_map_with_path(
            lambda path, _: any(getattr(k, "key", None) == cfg.inner_freeze
                                for k in path),
            abstract(model.specs(), dt))
    step = make_meta_step(model.loss_fn, mcfg, optimizer=opt, A=A,
                          combine_fn=combine_fn, freeze_mask=freeze_mask,
                          disagreement_fn=disagreement_fn)
    if agent_mesh:
        # agent dim on the agent axis; the task-batch dim rides intra-agent
        # data parallelism when the mesh has it (2D (agent, model) meshes
        # keep the per-agent batch local)
        fold_spec = (P("agent", None, "data") if intra_agent_data
                     else P("agent"))
    elif cfg.placement == "pod":
        fold_spec = P("pod" if multi_pod else None, None, "data")
    else:
        fold_spec = P(("pod", "data") if multi_pod else "data")

    def train_step(state: TrainState, batch: dict):
        support, query = split_meta_batch(cfg, batch, K, T, tb,
                                          fold_spec=fold_spec, mesh=mesh)
        return step(state, support, query)

    opt_abs = jax.eval_shape(opt.init, p_abs)
    o_axes = opt_state_axes(cfg.outer_optimizer, p_axes)
    opt_sh = tree_shardings(o_axes, opt_abs, rules, mesh) if o_axes != () else ()
    state_abs = TrainState(jax.ShapeDtypeStruct((), jnp.int32), p_abs, opt_abs)
    state_sh = TrainState(NamedSharding(mesh, P()), params_sh, opt_sh)

    in_axes_map = input_axes(cfg, shape_name)
    in_specs = input_specs(cfg, shape_name)
    batch_sh = tree_shardings(in_axes_map, in_specs, rules, mesh)

    def init_state_fn(seed: int = 0) -> TrainState:
        keys = jax.random.split(jax.random.key(seed), K)
        params = jax.vmap(lambda k: model.init(k, out_dt))(keys)
        return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))

    return TrainBundle(cfg, mesh, K, T, tb, train_step, state_abs, state_sh,
                       batch_sh, init_state_fn, loss_fn=model.loss_fn,
                       mcfg=mcfg, schedule=sched, outer_dtype=outer_dtype,
                       combine_dtype=wire_dtype,
                       combine_backend=backend,
                       disagreement_backend=disagreement_backend)


# ---------------------------------------------------------------------------
# Superstep: C meta-steps per dispatch (the dispatch-free training loop)
# ---------------------------------------------------------------------------

# Scalar step metrics carried out of the scan — one (C,) array per key, so a
# C-step dispatch costs ONE host fetch instead of C device syncs.  Per-agent
# metrics (K-vectors) stay inside the step; consumers that need them run at
# C=1 or via the eval harness.
SUPERSTEP_METRICS = ("loss", "disagreement")


def make_superstep(step_fn):
    """Fold ``step_fn`` into ``superstep(state, batches) -> (state, metrics)``.

    ``batches``: the pytree of one meta-batch with an extra leading
    dispatch axis of size C (``TrainBundle.make_pipeline(stack=C)``'s
    layout).  The C meta-steps run inside one ``lax.scan`` — a single
    jitted, buffer-donatable call, so the Python loop dispatches (and
    syncs metrics to host) once per C steps instead of once per step.
    ``metrics`` maps each :data:`SUPERSTEP_METRICS` key to a ``(C,)``
    device array (step-resolved, fetched in one transfer).

    Step-for-step identical to calling ``step_fn`` C times: the scan body
    IS the per-step function, and the batch sequence is the same because
    the stacked pipeline groups — never reorders — episodes.
    """

    def superstep(state, batches):
        def body(st, batch):
            st, metrics = step_fn(st, batch)
            return st, {k: metrics[k] for k in SUPERSTEP_METRICS}

        return jax.lax.scan(body, state, batches)

    return superstep


# ---------------------------------------------------------------------------
# Prefill step (inference-prefill: full-sequence forward)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PrefillBundle:
    cfg: ArchConfig
    mesh: Mesh
    step_fn: Any                  # (params, batch) -> logits
    params_specs: Any
    params_shardings: Any
    batch_shardings: Any


def build_prefill(cfg: ArchConfig, mesh: Mesh, shape_name: str | InputShape
                  ) -> PrefillBundle:
    """Inference prefill: one full-sequence forward of the launch model
    (no agent axis, no meta step) producing next-token logits."""
    dt = DTYPES[cfg.dtype]
    # inference uses the GShard one-hot MoE dispatch where the dispatch/
    # expert flop ratio allows (−75% FLOPs/dev, −91% wire on jamba/mixtral
    # prefill; 'auto' keeps sort/gather for high-k small-f MoEs like
    # DeepSeek where the one-hot einsum would exceed the expert GEMMs) —
    # EXPERIMENTS HC2
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, moe_dispatch="auto")
    model = build_model(cfg)
    model.act_sharding = NamedSharding(mesh, P("data", None, None))

    def prefill_step(params, batch):
        return model.forward(params, batch)

    rules = rules_for(cfg, mesh, kind="decode")
    p_specs = model.specs()
    p_abs = abstract(p_specs, dt)
    params_sh = tree_shardings(axes_tree(p_specs), p_abs, rules, mesh)
    in_specs = {k: v for k, v in input_specs(cfg, shape_name).items()}
    axes = {"tokens": ("batch", None), "labels": ("batch", None)}
    if cfg.arch_type == "audio":
        axes["encoder_frames"] = ("batch", None, "embed")
    if cfg.arch_type == "vlm":
        axes["image_patches"] = ("batch", None, "embed")
    batch_sh = tree_shardings(axes, in_specs, rules, mesh)
    return PrefillBundle(cfg, mesh, prefill_step, p_abs, params_sh, batch_sh)


# ---------------------------------------------------------------------------
# Serve step (single-token decode against a KV cache)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeBundle:
    cfg: ArchConfig
    mesh: Mesh
    step_fn: Any                  # (params, cache, token, pos) -> (logits, cache)
    params_specs: Any
    params_shardings: Any
    input_shardings: Any          # dict for {token,pos,cache}


def build_serve(cfg: ArchConfig, mesh: Mesh,
                shape_name: str | InputShape) -> ServeBundle:
    shape = resolve_input_shape(shape_name)
    assert shape.kind == "decode"
    dt = DTYPES[cfg.dtype]
    model = build_model(cfg)

    def serve_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)

    rules = rules_for(cfg, mesh, kind="decode")
    p_specs = model.specs()
    p_axes = axes_tree(p_specs)
    p_abs = abstract(p_specs, dt)
    params_sh = tree_shardings(p_axes, p_abs, rules, mesh)
    in_specs = input_specs(cfg, shape_name)
    in_axes_map = input_axes(cfg, shape_name)
    input_sh = tree_shardings(in_axes_map, in_specs, rules, mesh)
    return ServeBundle(cfg, mesh, serve_step, p_abs, params_sh, input_sh)
