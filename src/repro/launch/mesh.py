"""Mesh construction — the one owner of the mesh-axis contract.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  ``make_host_mesh`` builds the trainer's
mesh over the devices that are present.  ``make_production_mesh`` is the
pod-scale mesh of the dry run and the compiled-program lint, whose
entrypoints set ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import.

Mesh-axis contract
==================

Every mesh in this repo is built from (a subset of) four named axes:

``agent``   one Dif-MAML learner per slice — the decentralized diffusion
            graph lives on this axis and on nothing else.  When present it
            is the leading axis, the ``agent`` *logical* axis of the
            stacked parameter tree maps onto it 1:1
            (``sharding/rules.py``), and the ``mesh_sparse`` /
            ``mesh_sparse_dynamic`` combine backends shard_map their
            ``lax.ppermute`` rounds over it (they require extent == K, one
            agent per shard — see :mod:`repro.core.diffusion`).
``data``    intra-agent batch/FSDP parallelism.  On legacy meshes without
            an ``agent`` axis it doubles as the agent axis for
            ``placement='data'`` archs (one agent per data slice).
``model``   tensor parallelism (ffn/heads/experts/vocab candidates in
            ``sharding/rules.py``); never carries agents.
``pod``     legacy multi-pod axis.  Before the ``agent`` axis existed,
            ``placement='pod'`` archs put one agent per pod and
            ``placement='data'`` archs tiled agents over ``(pod, data)``.
            On agent-axis meshes ``pod`` retires: the agent graph is
            ``agent`` and everything inside an agent is ``data``/``model``,
            regardless of ``cfg.placement``.

``make_production_mesh(agents=K)`` composes the axes at production scale:
each agent's K-th slice of the parameter stack is itself TP/FSDP-sharded
over the remaining ``data``/``model`` extents, which is what lets the big
configs (qwen2_7b, mixtral_8x22b, deepseek_v2_lite) run decentralized.
"""
from __future__ import annotations

from typing import Sequence

import jax

from repro import compat
from repro.compat import mesh_axis_sizes

__all__ = ["make_production_mesh", "make_host_mesh", "mesh_axis_sizes"]

# One pod = 256 chips (16×16); the multi-pod budget doubles it.
_POD_DEVICES = 256


def make_production_mesh(*, multi_pod: bool = False,
                         agents: int | None = None,
                         model: int = 16) -> jax.sharding.Mesh:
    """Production mesh.

    ``agents=None`` (legacy): ``(data, model)`` = 16×16 single-pod or
    ``(pod, data, model)`` = 2×16×16 two-pod — the agent graph rides the
    ``data``/``pod`` axes per ``cfg.placement``.

    ``agents=K``: an agent-axis mesh over the same device budget (256
    single-pod, 512 with ``multi_pod``): ``(agent, data, model)`` with
    ``data = budget // (K · model)``, collapsing to 2D ``(agent, model)``
    when the data extent is 1.  ``K · model`` must divide the budget —
    a non-factoring request raises with both numbers instead of silently
    dropping devices.
    """
    if agents is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return compat.make_mesh(shape, axes)
    budget = 2 * _POD_DEVICES if multi_pod else _POD_DEVICES
    if agents < 1 or model < 1 or budget % (agents * model):
        raise ValueError(
            f"agent mesh does not factor: agents={agents} × model={model} "
            f"must divide the {budget}-device "
            f"{'two-pod' if multi_pod else 'single-pod'} budget "
            f"(got {agents * model})")
    data = budget // (agents * model)
    if data == 1:
        return compat.make_mesh((agents, model), ("agent", "model"))
    return compat.make_mesh((agents, data, model), ("agent", "data", "model"))


def make_host_mesh(data: int = 1, model: int = 1, *,
                   agents: int | None = None,
                   devices: Sequence[jax.Device] | None = None
                   ) -> jax.sharding.Mesh:
    """Mesh over the devices that are present (``devices``, default every
    device of the process) — what the trainer and the serve engine run on.

    Legacy form: ``(data, model)``.  With ``agents=K``: ``(agent, data,
    model)``, collapsing to ``(agent, model)`` when ``data == 1`` — one
    agent per ``agent`` slice.  The product of the extents must divide the
    device count; the mesh takes the first that many devices.  A geometry
    that does not factor raises with both numbers: a clamp would change K
    (or the batch split) under the caller.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    extents = (agents or 1) * data * model
    if min(agents or 1, data, model) < 1 or n % extents:
        head = f"agents={agents} × " if agents is not None else ""
        raise ValueError(
            f"host mesh does not factor: {head}data={data} × model={model} "
            f"= {extents} must divide the {n} available device(s)")
    if agents is None:
        return compat.make_mesh((data, model), ("data", "model"),
                                devices=devices)
    if data == 1:
        return compat.make_mesh((agents, model), ("agent", "model"),
                                devices=devices)
    return compat.make_mesh((agents, data, model),
                            ("agent", "data", "model"), devices=devices)
