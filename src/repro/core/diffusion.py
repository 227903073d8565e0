"""Diffusion (Adapt-then-Combine) strategy over a stacked agent axis.

All per-agent launch models are stored with a leading ``K`` (agent) axis on
every parameter leaf.  The combine step (paper eq. 6b)

    w_{k,i} = Σ_l a_{lk} φ_{l,i}

is a contraction over that axis — the algorithm's only communication point.
This module is the single home for every implementation of that contraction,
organized as a **backend registry** behind one entry point,
:func:`make_combine`.  Trainer (``core/meta_trainer.py``), launch
(``launch/steps.py``) and benchmarks (``benchmarks/run.py``) all build their
combine through it.

Registered backends
===================

``dense``        einsum against the full K×K matrix.  Under pjit with the
                 agent axis sharded over a mesh axis, XLA lowers this to
                 all-gather + local reduction: O(K·|w|) collective bytes.
                 Paper-faithful baseline semantics for arbitrary graphs.
``sparse_host``  host-roll emulation of the ppermute schedule: one weighted
                 ``jnp.roll`` per circular neighbor offset.  Under GSPMD a
                 roll on the agent-sharded dim lowers to collective-permutes
                 of one shard per offset: O(deg·|w|) bytes.  Exact for *any*
                 A (offsets with partial support get elementwise-zero
                 weights), efficient when A is a union of few circular
                 offsets (ring, torus-on-agent-axis, full graph).
``sparse``       ``lax.ppermute`` schedule, to be called *inside* an
                 existing shard_map/manual context where the agent axis is
                 one-agent-per-shard.
``mesh_sparse``  production sparse combine: the ``sparse`` schedule wrapped
                 in a partial-manual shard_map over the agent mesh axis
                 (built via :mod:`repro.compat`).  Requires jit.
``sparse_host_dynamic``
                 host-roll lowering of a *dynamic* (stacked ``(S, K, K)``)
                 schedule via its :class:`repro.core.topology.ScheduleIR`:
                 one weighted roll per offset in the period's offset
                 *union*, with the per-step weight rows gathered by the
                 traced step index.  Exact for every schedule kind
                 (inactive offsets carry zero weights that step); under
                 GSPMD each roll stays a collective-permute, so dynamic
                 graphs keep O(deg·|w|) wire instead of the O(K·|w|) the
                 dense step-indexed einsum pays.
``sparse_dynamic``
                 the same IR lowered to ``lax.ppermute`` rounds, to be
                 called *inside* an existing shard_map/manual context
                 (one-agent-per-shard); the permute set is fixed across
                 steps — only the weight gather sees the step — so one
                 jitted program serves the whole schedule.
``mesh_sparse_dynamic``
                 production dynamic combine: ``sparse_dynamic`` wrapped in
                 a partial-manual shard_map over the agent mesh axis, step
                 threaded in replicated.  Requires jit.
``pallas``       the fused :mod:`repro.kernels.dif_combine` TPU kernel:
                 one pass over the parameter bytes instead of K−1 separate
                 axpy passes.  Arbitrary parameter pytrees are served
                 through the flatten-to-(K, M) pack/unpack path below
                 (lane-aligned zero padding, one kernel launch per dtype
                 group).  ``interpret=True`` runs the same kernel on CPU.
``centralized``  every agent receives the centroid (fully-connected uniform
                 A = (1/K)11ᵀ): the paper's centralized reference.
``none``         identity: the non-cooperative baseline (A = I).

Agent mesh axis
===============

The ``mesh_sparse`` / ``mesh_sparse_dynamic`` backends require the agent
axis they shard_map over to hold exactly one agent per shard (extent == K).
Two mesh generations satisfy this (the full contract lives in
``launch/mesh.py``):

* legacy meshes, where the agent graph rides ``data`` (or ``pod`` for
  ``placement='pod'`` archs) — valid only when that axis extent equals K;
* agent-axis meshes (``make_production_mesh(agents=K)``), where ``agent``
  is a dedicated leading axis composed with intra-agent ``data`` (FSDP)
  and ``model`` (TP) axes.  Here ``in_specs`` must carry each leaf's real
  sharding (agent axis *plus* its TP/FSDP axes) so the ppermute rounds
  move only the per-agent *shard* — deg·(per-device shard bytes) on the
  wire — while the model-axis collectives of the surrounding step stay
  untouched.  :func:`select_backend` defaults ``axis_name`` to ``'agent'``
  on such meshes.

Wire format
===========

The ppermute backends (``sparse``/``mesh_sparse`` and their ``*_dynamic``
siblings) take a ``combine_dtype`` — the dtype φ travels in on the
collective-permute rounds:

``"bfloat16"``   half-width wire.  Each leaf is rounded to bf16 **once**
                 and bitcast to ``uint16`` before the permute rounds, so
                 no backend pass can silently widen the transfer (XLA:CPU's
                 float normalization upcasts bf16 collectives to f32;
                 integer collectives are left alone on every backend, and
                 on TPU the bitcast is free).  Every received payload is
                 bitcast back and the weighted mix is **accumulated in
                 f32**, with the self-term taken from the local full-
                 precision value — one rounding on the wire, none
                 compounding across rounds — then cast back to the leaf
                 dtype once.  Combine wire bytes drop 2× vs the f32 wire.
``"float32"``    full-width wire: φ is promoted to f32 for the rounds and
                 the mix accumulates in f32 (the escape hatch when bf16
                 parity is in question).
``None``         legacy behavior: rounds and accumulation in the leaf's
                 own dtype (kept for direct callers; the launch layer
                 always resolves a concrete wire dtype).

:func:`resolve_combine_dtype` owns the default: the wire is bf16 exactly
when the outer (param/grad) dtype is bf16, and ``--combine-dtype f32``
overrides it.  :func:`wire_elem_bytes` maps the resolved name to the
per-element wire bytes the budget checks (``tree_shard_bytes`` /
``agent_combine_check`` / ``AGENT_MESH_BUDGETS``) must size against.

Backend selection
=================

``make_combine("auto", A=A, mesh=..., axis_name=...)`` picks by topology,
mesh and accelerator:

  1. K == 1                                  → ``none``
  2. stacked ``(S, K, K)`` schedule whose offset union is sparse
     (deg < K−1): on a live mesh whose ``axis_name`` extent equals K
     → ``mesh_sparse_dynamic``; otherwise    → ``sparse_host_dynamic``
  3. stacked schedule with a dense offset union (e.g. gossip on the
     full graph)                             → ``dense`` (step-indexed)
  4. circular-offset-sparse static A (deg < K−1) on a live mesh whose
     ``axis_name`` extent equals K           → ``mesh_sparse``
  5. circular-offset-sparse static A, no mesh → ``sparse_host``
  6. dense A, no mesh, TPU backend           → ``pallas``
     (on a live mesh the packed layout would break leaf shardings,
     so dense-einsum keeps the GSPMD lowering)
  7. otherwise                               → ``dense``

:func:`resolve_schedule_backend` routes an explicitly-requested static
sparse backend (``sparse``/``sparse_host``/``mesh_sparse``) to its
``*_dynamic`` sibling when the matrix is a stacked schedule — the permute
rounds and wire cost are identical, only the weight gather becomes
step-indexed — and only falls back to ``dense`` (loudly) for backends with
no dynamic form.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

PyTree = Any
# Every registered backend returns ``combine(phi, step=None)``: the optional
# traced step index selects the current matrix of a stacked ``(S, K, K)``
# schedule (static matrices ignore it), so dynamic graphs stay inside one
# jit-compiled step function.
CombineFn = Callable[..., PyTree]

__all__ = [
    "resolve_combine_dtype",
    "wire_elem_bytes",
    "dense_combine",
    "sparse_combine_host",
    "make_sparse_combine",
    "make_mesh_sparse_combine",
    "make_sparse_host_dynamic_combine",
    "make_sparse_dynamic_combine",
    "make_mesh_sparse_dynamic_combine",
    "make_pallas_combine",
    "pack_pytree",
    "centralized_combine",
    "no_combine",
    "CombineBackend",
    "register_backend",
    "combine_backends",
    "select_backend",
    "resolve_schedule_backend",
    "make_combine",
    "atc_step",
    "cta_step",
    "disagreement",
    "centroid",
]

LANE = 128                 # TPU vector lane width; pallas pad granularity

# Wire dtypes the ppermute backends can put on the combine rounds, with the
# per-element wire bytes every budget check must size against.
WIRE_DTYPES = {"bfloat16": 2, "float32": 4}


def resolve_combine_dtype(outer_dtype: str, override: str | None = None
                          ) -> str:
    """The wire dtype of the sparse combine rounds (module docstring, "Wire
    format"): bf16 exactly when the outer (param/grad) dtype is bf16, f32
    otherwise; ``override`` (the ``--combine-dtype`` escape hatch) wins."""
    chosen = override or ("bfloat16" if outer_dtype == "bfloat16"
                          else "float32")
    if chosen not in WIRE_DTYPES:
        raise ValueError(
            f"combine_dtype {chosen!r} is not a supported wire format; "
            f"pick one of {sorted(WIRE_DTYPES)}")
    return chosen


def wire_elem_bytes(combine_dtype: str) -> int:
    """Per-element bytes the combine's permute rounds put on the wire."""
    return WIRE_DTYPES[combine_dtype]


def _wire_encode(x):
    """One rounding to bf16, shipped as its u16 bit pattern: integer
    collectives dodge every float-widening backend pass (XLA:CPU's float
    normalization upcasts bf16 collectives to f32), so the permute result
    is 2 bytes/elem in the *optimized* HLO on every backend."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)


def _wire_decode(r):
    """Received u16 payload -> f32 for the accumulation."""
    return jax.lax.bitcast_convert_type(r, jnp.bfloat16).astype(jnp.float32)


def _circular_offsets(A: np.ndarray) -> list[int]:
    """Offsets d in [1, K) with any nonzero weight a_{(k-d) mod K, k}."""
    K = A.shape[0]
    return [d for d in range(1, K)
            if any(A[(k - d) % K, k] > 0 for k in range(K))]


def _spec_axes(specs: PyTree) -> list[set[str]]:
    """For each PartitionSpec leaf of ``specs``, the mesh axes it shards."""
    from jax.sharding import PartitionSpec

    return [{a for part in s if part is not None
             for a in ((part,) if isinstance(part, str) else part)}
            for s in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec))]


# ---------------------------------------------------------------------------
# Combine implementations
# ---------------------------------------------------------------------------

def dense_combine(A: jax.Array, phi: PyTree) -> PyTree:
    """w_new[k] = Σ_l A[l, k] φ[l] on the leading agent axis of each leaf."""

    def leaf(x):
        return jnp.einsum("lk,l...->k...", A.astype(x.dtype), x)

    return jax.tree.map(leaf, phi)


def sparse_combine_host(A: np.ndarray, phi: PyTree) -> PyTree:
    """Single-host emulation of the ppermute schedule using jnp.roll.

    Identical math to :func:`make_sparse_combine`; under GSPMD with the
    agent dim sharded, each roll lowers to a collective-permute while every
    other (TP) dim keeps its sharding.
    """
    A = np.asarray(A)
    K = A.shape[0]
    offsets = _circular_offsets(A)
    self_w = jnp.asarray(np.diagonal(A).copy())
    off_w = {d: jnp.asarray(np.array([A[(k - d) % K, k] for k in range(K)]))
             for d in offsets}

    def leaf(x):
        shape = (K,) + (1,) * (x.ndim - 1)
        acc = x * self_w.astype(x.dtype).reshape(shape)
        for d in offsets:
            # agent k receives from agent (k - d) mod K  ==  roll by +d
            acc = acc + (off_w[d].astype(x.dtype).reshape(shape)
                         * jnp.roll(x, d, axis=0))
        return acc

    return jax.tree.map(leaf, phi)


def make_sparse_combine(A: np.ndarray, axis_name: str,
                        wire_dtype: str | None = None) -> CombineFn:
    """Collective-permute combine, to be called *inside* shard_map where the
    leading agent axis is sharded one-agent-per-shard over ``axis_name``.

    Each circular offset ``d`` with any nonzero weight contributes one
    ``lax.ppermute`` (collective-permute over ICI) plus a per-destination
    weight multiply.  Self weights are a local scale.  Total collective
    bytes = (#offsets) · wire_elem_bytes · |w| vs. (K-1)/K · K · |w| for
    the all-gather that XLA emits for the dense einsum.

    ``wire_dtype``: the wire-format contract of the module docstring —
    'bfloat16' ships each leaf's one-time bf16 rounding as u16 and
    accumulates the mix in f32; 'float32' promotes to f32 for the rounds;
    None keeps the legacy in-dtype math."""
    A = np.asarray(A)
    K = A.shape[0]
    offsets = _circular_offsets(A)
    self_w = np.diagonal(A).copy()
    off_w = {d: np.array([A[(k - d) % K, k] for k in range(K)]) for d in offsets}
    half = wire_dtype == "bfloat16"

    def combine(phi: PyTree) -> PyTree:
        k = jax.lax.axis_index(axis_name)

        def leaf(x):
            # x: local block (1, ...) — one agent per shard.
            if wire_dtype is None:
                acc = x * jnp.asarray(self_w, x.dtype)[k]
                for d in offsets:
                    perm = [(l, (l + d) % K) for l in range(K)]
                    recv = jax.lax.ppermute(x, axis_name, perm)
                    acc = acc + recv * jnp.asarray(off_w[d], x.dtype)[k]
                return acc
            # f32 accumulation; only neighbor terms pass through the wire
            send = _wire_encode(x) if half else x.astype(jnp.float32)
            acc = x.astype(jnp.float32) * jnp.asarray(self_w, jnp.float32)[k]
            for d in offsets:
                perm = [(l, (l + d) % K) for l in range(K)]
                recv = jax.lax.ppermute(send, axis_name, perm)
                r32 = _wire_decode(recv) if half else recv
                acc = acc + r32 * jnp.asarray(off_w[d], jnp.float32)[k]
            return acc.astype(x.dtype)

        return jax.tree.map(leaf, phi)

    return combine


def make_mesh_sparse_combine(A: np.ndarray, mesh, axis_name: str,
                             in_specs: PyTree | None = None,
                             wire_dtype: str | None = None) -> CombineFn:
    """Production sparse combine: shard_map over the agent mesh axis with the
    ppermute schedule of :func:`make_sparse_combine`.  The agent axis is
    manual; all other axes (e.g. 'model' tensor parallelism) stay auto.
    Partial-manual shard_map must run under jit (both JAX lines).

    ``in_specs``: pytree of PartitionSpecs matching phi's *actual* shardings
    (agent dim on ``axis_name`` plus whatever TP axes each leaf carries).
    Omitting the TP axes would make shard_map all-gather every TP-sharded
    parameter at entry — measured +77% step wire bytes on qwen2-1.5b — so
    callers must pass the real specs for TP-sharded trees.

    Wire bytes per device for the exchange itself: (#circular offsets) ×
    |w_local|, vs. (K−1)/K × K × |w_local| for the dense-einsum all-gather."""
    from jax.sharding import PartitionSpec as _P

    inner = make_sparse_combine(A, axis_name, wire_dtype=wire_dtype)
    specs = in_specs if in_specs is not None else _P(axis_name)
    # Every axis the specs mention must be manual; any remaining mesh axis
    # stays auto (partial-manual mode — fine on TPU, but XLA:CPU cannot
    # partition it, so CPU callers should pass specs covering their axes).
    manual = {axis_name}.union(*_spec_axes(specs))

    def combine(phi: PyTree) -> PyTree:
        return compat.shard_map(
            inner, mesh, in_specs=(specs,), out_specs=specs,
            axis_names=manual)(phi)

    return combine


# ---------------------------------------------------------------------------
# Dynamic-schedule sparse combines: fixed ppermute rounds over the period's
# offset union, per-step weights gathered with the traced step index
# ---------------------------------------------------------------------------

def _ir_for(A):
    """Accept a ScheduleIR, a (K, K) matrix, or a stacked (S, K, K)
    schedule and return the ScheduleIR lowering."""
    from repro.core import topology
    if isinstance(A, topology.ScheduleIR):
        return A
    return topology.schedule_ir(np.asarray(A))


def _schedule_step(step, S: int):
    """The traced row index into the (S, ...) weight tables."""
    if step is None:
        if S != 1:
            raise ValueError(
                "a dynamic matrix schedule needs the step index: call "
                "combine(phi, step)")
        return jnp.zeros((), jnp.int32)
    return jnp.mod(step, S)


def make_sparse_host_dynamic_combine(ir) -> CombineFn:
    """Host-roll lowering of a dynamic schedule: one weighted ``jnp.roll``
    per offset in the period's union, weights gathered at ``step % S``.

    Identical math to the dense step-indexed einsum for *every* schedule
    kind (an offset inactive at some step carries elementwise-zero weights
    there).  Under GSPMD with the agent dim sharded each roll lowers to a
    collective-permute of one shard — O(deg·|w|) wire per combine, where
    deg is the offset-union size, vs O(K·|w|) for the dense gather."""
    K, S, offsets = ir.K, ir.period, ir.offsets
    self_w = jnp.asarray(ir.self_weights)        # (S, K)
    off_w = jnp.asarray(ir.offset_weights)       # (S, D, K)

    def combine(phi: PyTree, step=None) -> PyTree:
        s = _schedule_step(step, S)
        sw = jax.lax.dynamic_index_in_dim(self_w, s, keepdims=False)
        ow = jax.lax.dynamic_index_in_dim(off_w, s, keepdims=False)

        def leaf(x):
            shape = (K,) + (1,) * (x.ndim - 1)
            acc = x * sw.astype(x.dtype).reshape(shape)
            for i, d in enumerate(offsets):
                # agent k receives from agent (k - d) mod K == roll by +d
                acc = acc + (ow[i].astype(x.dtype).reshape(shape)
                             * jnp.roll(x, d, axis=0))
            return acc

        return jax.tree.map(leaf, phi)

    return combine


def make_sparse_dynamic_combine(ir, axis_name: str,
                                wire_dtype: str | None = None) -> CombineFn:
    """``lax.ppermute`` lowering of a dynamic schedule, to be called
    *inside* shard_map with the agent axis one-agent-per-shard over
    ``axis_name``.

    The permute set is the period's offset union — fixed across steps, so
    the whole schedule compiles to one program; only the weight gather
    (two scalar loads per round from the (S, ·, K) tables) sees the step.
    Wire bytes per combine: D · wire_elem_bytes · |w_local| with D = deg
    of the union.  ``wire_dtype`` follows the module-docstring wire-format
    contract (None = legacy in-dtype math)."""
    K, S, offsets = ir.K, ir.period, ir.offsets
    np_self_w = np.asarray(ir.self_weights, np.float32)     # (S, K)
    np_off_w = np.asarray(ir.offset_weights, np.float32)    # (S, D, K)
    half = wire_dtype == "bfloat16"

    def combine(phi: PyTree, step=None) -> PyTree:
        s = _schedule_step(step, S)
        k = jax.lax.axis_index(axis_name)
        sw = jnp.asarray(np_self_w)[s, k]
        ow = jnp.asarray(np_off_w)[s, :, k]      # (D,) this agent's weights

        def leaf(x):
            if wire_dtype is None:
                acc = x * sw.astype(x.dtype)
                for i, d in enumerate(offsets):
                    perm = [(l, (l + d) % K) for l in range(K)]
                    recv = jax.lax.ppermute(x, axis_name, perm)
                    acc = acc + recv * ow[i].astype(x.dtype)
                return acc
            # f32 accumulation; only neighbor terms pass through the wire
            send = _wire_encode(x) if half else x.astype(jnp.float32)
            acc = x.astype(jnp.float32) * sw
            for i, d in enumerate(offsets):
                perm = [(l, (l + d) % K) for l in range(K)]
                recv = jax.lax.ppermute(send, axis_name, perm)
                r32 = _wire_decode(recv) if half else recv
                acc = acc + r32 * ow[i]
            return acc.astype(x.dtype)

        return jax.tree.map(leaf, phi)

    return combine


def make_mesh_sparse_dynamic_combine(ir, mesh, axis_name: str,
                                     in_specs: PyTree | None = None,
                                     wire_dtype: str | None = None
                                     ) -> CombineFn:
    """Production dynamic combine: shard_map over the agent mesh axis with
    the :func:`make_sparse_dynamic_combine` rounds; the step index rides in
    replicated.  Same in_specs contract as :func:`make_mesh_sparse_combine`
    (pass the real leaf specs for TP-sharded trees or shard_map all-gathers
    them at entry)."""
    from jax.sharding import PartitionSpec as _P

    inner = make_sparse_dynamic_combine(ir, axis_name, wire_dtype=wire_dtype)
    specs = in_specs if in_specs is not None else _P(axis_name)
    manual = {axis_name}.union(*_spec_axes(specs))

    def combine(phi: PyTree, step=None) -> PyTree:
        if step is None:
            _schedule_step(step, ir.period)      # raise early when S > 1
            step = jnp.zeros((), jnp.int32)
        return compat.shard_map(
            inner, mesh, in_specs=(specs, _P()), out_specs=specs,
            axis_names=manual)(phi, step)

    return combine


def make_mesh_disagreement(mesh, axis_name: str, in_specs: PyTree
                           ) -> Callable[[PyTree], jax.Array]:
    """:func:`disagreement` for parameters held one agent per shard of the
    mesh axis ``axis_name``, with the leaf specs ``in_specs`` (the same
    contract as :func:`make_mesh_sparse_dynamic_combine`).

    Left to GSPMD, the agent mean of :func:`disagreement` is an f32
    all-reduce of every leaf at full shape.  Here each shard's block is
    split K ways along its first dimension that K divides (a block with
    none is flattened and zero-padded to a multiple of K: a pad's agent
    mean is zero, so it adds nothing); one tiled ``all_to_all`` gives shard
    c the c-th piece of every agent's block (bf16 rides as ``u16``, see
    ``_wire_encode``), a quarter of the all-reduce's bytes at K = 4 on a
    bf16 wire.  The per-element arithmetic is :func:`disagreement`'s, so
    under jit the two differ only in the f32 summation order.  Each leaf's
    squared deviations are summed over the axes its spec shards (a leaf
    replicated over an axis is counted once) by one scalar ``psum`` per
    axis set."""
    from jax.sharding import PartitionSpec as _P

    K = compat.mesh_axis_sizes(mesh)[axis_name]
    specs = jax.tree.leaves(in_specs, is_leaf=lambda x: isinstance(x, _P))
    sums_over = [tuple(a for a in mesh.axis_names
                       if a == axis_name or a in axes)
                 for axes in _spec_axes(specs)]

    def local(leaves):
        sums: dict[tuple, jax.Array] = {}
        for x, axes in zip(leaves, sums_over):
            d = next((d for d in range(1, x.ndim) if x.shape[d] % K == 0),
                     None)
            if d is None:
                flat = x.reshape(-1)
                x = jnp.pad(flat, (0, -flat.size % K)).reshape(1, -1)
                d = 1
            # the K pieces on an axis of their own, split in place along a
            # dimension K divides: flattening a tiled block copies it
            x = x.reshape(x.shape[:d] + (K, x.shape[d] // K) + x.shape[d + 1:])
            if x.dtype == jnp.bfloat16:
                wire = jax.lax.bitcast_convert_type(x, jnp.uint16)
                got = jax.lax.all_to_all(wire, axis_name, d, 0, tiled=True)
                rows = jax.lax.bitcast_convert_type(got, jnp.bfloat16)
            else:
                rows = jax.lax.all_to_all(x, axis_name, d, 0, tiled=True)
            xc = jnp.mean(rows, axis=0, keepdims=True)
            sq = jnp.sum((rows - xc).astype(jnp.float32) ** 2)
            sums[axes] = sums.get(axes, 0.0) + sq
        total = sum(jax.lax.psum(v, axes) for axes, v in sums.items())
        return total / K

    def disagreement_fn(params: PyTree) -> jax.Array:
        leaves = jax.tree.leaves(params)
        return compat.shard_map(local, mesh, in_specs=(specs,),
                                out_specs=_P())(leaves)

    return disagreement_fn


def centralized_combine(phi: PyTree) -> PyTree:
    """All agents receive the network centroid: A = (1/K) 1 1ᵀ."""

    def leaf(x):
        return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)

    return jax.tree.map(leaf, phi)


def no_combine(phi: PyTree) -> PyTree:
    return phi


# ---------------------------------------------------------------------------
# Pallas backend: flatten-to-(K, M) pack/unpack so the fused kernel serves
# arbitrary parameter pytrees (ragged leaf sizes, mixed dtypes)
# ---------------------------------------------------------------------------

def pack_pytree(phi: PyTree, block_m: int = 512
                ) -> tuple[list[jax.Array], Callable[[list[jax.Array]], PyTree]]:
    """Pack a pytree of (K, ...) leaves into one (K, M_pad) buffer per dtype.

    Leaves are flattened to (K, m_i) and concatenated along the feature dim,
    then zero-padded so M_pad is the smallest multiple of ``block_m`` (keep
    ``block_m`` a multiple of the 128-lane width for full-width VPU
    reductions) covering the group.  Because the combine is linear and the
    pad is zero, padded columns stay zero through the kernel and are sliced
    off on unpack.

    Returns ``(buffers, unpack)`` where ``unpack`` maps same-shaped combined
    buffers back to the original pytree structure.
    """
    leaves, treedef = jax.tree.flatten(phi)
    if not leaves:
        return [], lambda bufs: jax.tree.unflatten(treedef, [])
    K = leaves[0].shape[0]
    groups: dict[Any, list[int]] = {}
    for i, x in enumerate(leaves):
        groups.setdefault(jnp.dtype(x.dtype), []).append(i)

    buffers: list[jax.Array] = []
    layout: list[tuple[list[int], list[tuple[int, ...]]]] = []
    for dt, idxs in groups.items():
        flats = [leaves[i].reshape(K, -1) for i in idxs]
        M = sum(f.shape[1] for f in flats)
        pad = (-M) % block_m
        if pad:
            flats.append(jnp.zeros((K, pad), dt))
        buffers.append(jnp.concatenate(flats, axis=1) if len(flats) > 1
                       else flats[0])
        layout.append((idxs, [leaves[i].shape for i in idxs]))

    def unpack(new_buffers: list[jax.Array]) -> PyTree:
        out: list[Any] = list(leaves)
        for buf, (idxs, shapes) in zip(new_buffers, layout):
            off = 0
            for i, shape in zip(idxs, shapes):
                n = int(np.prod(shape[1:], dtype=np.int64))
                out[i] = jax.lax.slice_in_dim(buf, off, off + n,
                                              axis=1).reshape(shape)
                off += n
        return jax.tree.unflatten(treedef, out)

    return buffers, unpack


def make_pallas_combine(A: np.ndarray | jax.Array, *, block_m: int = 512,
                        interpret: bool | None = None) -> CombineFn:
    """Fused dif_combine kernel over the packed (K, M) layout.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere
    (bitwise-identical math, lets CPU tests exercise the production path).
    """
    Aj = jnp.asarray(A)

    def combine(phi: PyTree) -> PyTree:
        return _pallas_apply(Aj, phi, block_m=block_m, interpret=interpret)

    return combine


# ---------------------------------------------------------------------------
# Backend registry + selection
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CombineBackend:
    """One registered combine implementation.

    ``build(A=..., axis_name=..., mesh=..., in_specs=..., block_m=...,
    interpret=...)`` returns a ``CombineFn``; builders ignore context keys
    they don't need.
    """
    name: str
    build: Callable[..., CombineFn]
    needs_matrix: bool = True
    needs_mesh: bool = False
    needs_axis_name: bool = False
    # Whether the lowered combine moves its payload over collective-permutes
    # — the backends the wire-dtype / deg·shard lint rules can reason about.
    # Dense/pallas/centralized combines exchange nothing (replicated math)
    # or use other collectives, so permute-based rules skip them.
    emits_permutes: bool = False


_BACKENDS: dict[str, CombineBackend] = {}


def register_backend(name: str, **flags: bool):
    """Decorator: register a combine builder under ``name``."""

    def deco(build: Callable[..., CombineFn]) -> Callable[..., CombineFn]:
        _BACKENDS[name] = CombineBackend(name, build, **flags)
        return build

    return deco


def combine_backends() -> tuple[str, ...]:
    return tuple(_BACKENDS)


def backend_lint_metadata(name: str, combine_dtype: str | None = None) -> dict:
    """What the compiled-program lint rules may assume about a backend.

    ``emits_permutes`` gates the permute-window rules (a dense/pallas
    combine exchanges nothing over collective-permutes); ``wire_hlo_dtype``
    is the HLO-level dtype the payload travels in — ``u16`` for the bf16
    bitcast wire, ``f32`` otherwise (see the wire-format contract above).
    """
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown combine backend {name!r}; "
            f"pick one of {sorted(_BACKENDS)}")
    b = _BACKENDS[name]
    return {
        "backend": name,
        "emits_permutes": b.emits_permutes,
        "wire_hlo_dtype": "u16" if combine_dtype == "bfloat16" else "f32",
    }


def _stepless(fn: Callable[[PyTree], PyTree]) -> CombineFn:
    """Adapt a static combine to the ``(phi, step=None)`` surface."""

    def combine(phi: PyTree, step=None) -> PyTree:
        return fn(phi)

    return combine


def _stacked(Aj: jax.Array, apply: Callable[[jax.Array, PyTree], PyTree]
             ) -> CombineFn:
    """Index a stacked ``(S, K, K)`` schedule with the traced step, then
    run ``apply(A_t, phi)`` — shared by every step-indexed backend."""
    S = Aj.shape[0]

    def combine(phi: PyTree, step=None) -> PyTree:
        if step is None:
            raise ValueError(
                "a stacked matrix schedule needs the step index: call "
                "combine(phi, step)")
        At = jax.lax.dynamic_index_in_dim(Aj, jnp.mod(step, S),
                                          keepdims=False)
        return apply(At, phi)

    return combine


# Static sparse backend -> its stacked-schedule-capable sibling: the same
# ppermute rounds, with the per-step weight rows gathered by the traced step.
_DYNAMIC_SIBLING = {"sparse": "sparse_dynamic",
                    "sparse_host": "sparse_host_dynamic",
                    "mesh_sparse": "mesh_sparse_dynamic"}


def _reject_stacked(A, name: str) -> np.ndarray:
    A = np.asarray(A)
    if A.ndim == 3:
        raise ValueError(
            f"combine backend {name!r} precomputes a static per-offset "
            f"permute schedule and cannot serve a stacked ({A.shape[0]}-"
            f"step) matrix schedule; use its dynamic sibling "
            f"{_DYNAMIC_SIBLING[name]!r} (same O(deg·|w|) ppermute rounds, "
            f"weights gathered with the traced step) — or the step-indexed "
            f"'dense'/'pallas' dense fallbacks")
    return A


@register_backend("dense")
def _build_dense(*, A, **_ctx) -> CombineFn:
    Aj = jnp.asarray(A)
    if Aj.ndim == 3:
        return _stacked(Aj, dense_combine)
    return _stepless(functools.partial(dense_combine, Aj))


@register_backend("sparse_host", emits_permutes=True)
def _build_sparse_host(*, A, **_ctx) -> CombineFn:
    return _stepless(functools.partial(
        sparse_combine_host, _reject_stacked(A, "sparse_host")))


@register_backend("sparse", needs_axis_name=True, emits_permutes=True)
def _build_sparse(*, A, axis_name, combine_dtype=None, **_ctx) -> CombineFn:
    return _stepless(make_sparse_combine(_reject_stacked(A, "sparse"),
                                         axis_name, wire_dtype=combine_dtype))


@register_backend("mesh_sparse", needs_mesh=True, needs_axis_name=True,
                  emits_permutes=True)
def _build_mesh_sparse(*, A, mesh, axis_name, in_specs=None,
                       combine_dtype=None, **_ctx) -> CombineFn:
    A = _reject_stacked(A, "mesh_sparse")
    K = A.shape[0]
    _check_agent_extent("mesh_sparse", mesh, axis_name, K)
    return _stepless(make_mesh_sparse_combine(A, mesh, axis_name,
                                              in_specs=in_specs,
                                              wire_dtype=combine_dtype))


def _check_agent_extent(name: str, mesh, axis_name: str, K: int) -> None:
    extent = compat.mesh_axis_sizes(mesh).get(axis_name)
    if extent != K:
        raise ValueError(
            f"{name} needs one agent per shard: axis {axis_name!r} has "
            f"extent {extent} but the schedule is over K={K} agents. Use "
            f"'sparse_host{'_dynamic' if 'dynamic' in name else ''}' when "
            f"the agent axis spans multiple mesh axes (e.g. multi-pod data "
            f"placement).")


@register_backend("sparse_host_dynamic", emits_permutes=True)
def _build_sparse_host_dynamic(*, A, **_ctx) -> CombineFn:
    return make_sparse_host_dynamic_combine(_ir_for(A))


@register_backend("sparse_dynamic", needs_axis_name=True,
                  emits_permutes=True)
def _build_sparse_dynamic(*, A, axis_name, combine_dtype=None, **_ctx
                          ) -> CombineFn:
    return make_sparse_dynamic_combine(_ir_for(A), axis_name,
                                       wire_dtype=combine_dtype)


@register_backend("mesh_sparse_dynamic", needs_mesh=True,
                  needs_axis_name=True, emits_permutes=True)
def _build_mesh_sparse_dynamic(*, A, mesh, axis_name, in_specs=None,
                               combine_dtype=None, **_ctx) -> CombineFn:
    ir = _ir_for(A)
    _check_agent_extent("mesh_sparse_dynamic", mesh, axis_name, ir.K)
    return make_mesh_sparse_dynamic_combine(ir, mesh, axis_name,
                                            in_specs=in_specs,
                                            wire_dtype=combine_dtype)


@register_backend("pallas")
def _build_pallas(*, A, block_m=512, interpret=None, **_ctx) -> CombineFn:
    Aj = jnp.asarray(A)
    if Aj.ndim == 3:
        return _stacked(Aj, functools.partial(_pallas_apply, block_m=block_m,
                                              interpret=interpret))
    return _stepless(make_pallas_combine(Aj, block_m=block_m,
                                         interpret=interpret))


@register_backend("fused")
def _build_fused(*, A, block_m=512, interpret=None, **_ctx) -> CombineFn:
    """Combine-only face of the fused outer backend.

    Selecting ``backend='fused'`` moves the whole clip→moments→combine
    chain into :func:`repro.core.fused.make_fused_outer` — the trainer
    threads that path itself.  The registry entry exists for the two spots
    that still need a plain combine under that name: the cta pre-mix (which
    runs *before* the gradient and therefore cannot fuse with the update)
    and direct ``make_combine('fused')`` callers; both get the packed
    one-pass pallas combine."""
    return _build_pallas(A=A, block_m=block_m, interpret=interpret)


def _pallas_apply(A: jax.Array, phi: PyTree, *, block_m: int = 512,
                  interpret: bool | None = None) -> PyTree:
    """One pallas combine against an already-selected (possibly traced)
    matrix."""
    from repro.kernels.dif_combine.dif_combine import dif_combine

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    buffers, unpack = pack_pytree(phi, block_m=block_m)
    outs = [dif_combine(A, buf, block_m=block_m, interpret=interpret)
            for buf in buffers]
    return unpack(outs)


@register_backend("centralized", needs_matrix=False)
def _build_centralized(**_ctx) -> CombineFn:
    return _stepless(centralized_combine)


@register_backend("none", needs_matrix=False)
def _build_none(**_ctx) -> CombineFn:
    return _stepless(no_combine)


def select_backend(A: np.ndarray | None, *, mesh=None,
                   axis_name: str | None = None) -> str:
    """Pick a backend name from topology, mesh and accelerator (see module
    docstring for the rule table).

    A mesh with a first-class ``agent`` axis announces the agent extent
    itself: when ``axis_name`` is not given it defaults to ``'agent'`` on
    such meshes, so 2D ``(agent, model)`` production meshes route sparse
    topologies to the shard_mapped backends without the caller having to
    know which mesh generation it is on."""
    if mesh is not None and axis_name is None:
        if "agent" in getattr(mesh, "axis_names", ()):
            axis_name = "agent"
    if A is None:
        return "dense"
    from repro.core import topology as _topo
    if isinstance(A, _topo.ScheduleIR):
        A = A.stacked()
    A = np.asarray(A)
    if A.ndim == 3:
        # stacked per-step schedule: a sparse offset union lowers to fixed
        # ppermute rounds with step-gathered weights; a dense union (e.g.
        # gossip on the full graph) keeps the step-indexed dense einsum
        ir = _ir_for(A)
        if ir.K == 1:
            return "none"
        if ir.degree < ir.K - 1:
            if (mesh is not None and axis_name is not None
                    and compat.mesh_axis_sizes(mesh).get(axis_name) == ir.K):
                return "mesh_sparse_dynamic"
            return "sparse_host_dynamic"
        return "dense"
    K = A.shape[0]
    if K == 1:
        return "none"
    degree = len(_circular_offsets(A))
    sparse_wins = degree < K - 1          # strictly fewer collectives than
    if sparse_wins and mesh is not None and axis_name is not None:
        if compat.mesh_axis_sizes(mesh).get(axis_name) == K:
            return "mesh_sparse"
    if sparse_wins:
        return "sparse_host"
    if mesh is None and jax.default_backend() == "tpu":
        # fused one-pass dense reduction; only off-mesh — pack_pytree's
        # concatenate would destroy leaf shardings on a live mesh, forcing
        # an all-gather of every TP shard
        return "pallas"
    return "dense"


# Backends able to serve a stacked (S, K, K) schedule with the traced step.
_STEP_INDEXED_BACKENDS = ("dense", "pallas", "fused", "sparse_dynamic",
                          "sparse_host_dynamic", "mesh_sparse_dynamic")


def resolve_schedule_backend(backend: str, A) -> str:
    """Route ``backend`` to a stacked-schedule-capable equivalent when ``A``
    is a stacked schedule ('auto' resolves itself in
    :func:`select_backend`).  The single owner of the capability list —
    trainer and launch both route through here.

    The static sparse backends upgrade silently to their ``*_dynamic``
    siblings: identical permute rounds and O(deg·|w|) wire, only the weight
    gather becomes step-indexed.  A backend with no dynamic form falls back
    to 'dense' — loudly, because that gives up the sparse wire cost."""
    if (backend != "auto" and A is not None
            and np.asarray(A).ndim == 3
            and backend not in _STEP_INDEXED_BACKENDS):
        b = _BACKENDS.get(backend)
        if b is not None and not b.needs_matrix:
            return backend           # matrix-free (none/centralized): no-op
        sibling = _DYNAMIC_SIBLING.get(backend)
        if sibling is not None:
            return sibling
        import warnings
        warnings.warn(
            f"combine backend {backend!r} cannot step-index a stacked "
            f"({np.asarray(A).shape[0]}-step) matrix schedule; falling back "
            f"to 'dense' — collective bytes rise from O(deg·|w|) to "
            f"O(K·|w|). Use a static schedule to keep {backend!r}.",
            RuntimeWarning, stacklevel=3)
        return "dense"
    return backend


def make_combine(strategy: str, A: np.ndarray | None = None,
                 axis_name: str | None = None, *, mesh=None,
                 in_specs: PyTree | None = None, block_m: int = 512,
                 interpret: bool | None = None,
                 combine_dtype: str | None = None) -> CombineFn:
    """Single entry point: build a combine fn from a backend name or 'auto'.

    ``strategy``: 'auto' | any :func:`combine_backends` name.  'auto'
    resolves via :func:`select_backend`.

    ``A`` may be one ``(K, K)`` matrix, a stacked ``(S, K, K)`` schedule
    (see :class:`repro.core.topology.TopologySchedule`), or — for the
    ``*_dynamic`` backends — a pre-lowered
    :class:`repro.core.topology.ScheduleIR`.  Stacked schedules are served
    at O(deg·|w|) wire by the ``sparse_dynamic`` family (fixed ppermute
    rounds, weights gathered with the step passed to
    ``combine(phi, step)``) and at O(K·|w|) by the step-indexed
    'dense'/'pallas' fallbacks.

    ``combine_dtype``: wire format for the ppermute backends (see the
    module docstring) — 'bfloat16' | 'float32' | None (legacy in-dtype).
    Backends without a wire (dense, pallas, host rolls, …) ignore it.
    """
    if strategy == "auto":
        strategy = select_backend(A, mesh=mesh, axis_name=axis_name)
    if combine_dtype is not None and combine_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"combine_dtype {combine_dtype!r} is not a supported wire "
            f"format; pick one of {sorted(WIRE_DTYPES)}")
    backend = _BACKENDS.get(strategy)
    if backend is None:
        raise ValueError(
            f"unknown combine strategy {strategy!r}; "
            f"registered: {combine_backends()}")
    if backend.needs_matrix:
        assert A is not None, f"{strategy!r} combine needs a matrix A"
    if backend.needs_axis_name:
        assert axis_name is not None, f"{strategy!r} combine needs axis_name"
    if backend.needs_mesh:
        assert mesh is not None, f"{strategy!r} combine needs a mesh"
    return backend.build(A=A, axis_name=axis_name, mesh=mesh,
                         in_specs=in_specs, block_m=block_m,
                         interpret=interpret, combine_dtype=combine_dtype)


def combine_wire_bytes(A: np.ndarray, strategy: str, model_bytes: int) -> int:
    """Per-step collective-byte model for a backend (benchmark reporting).

    ``model_bytes``: size of one agent's launch model.  dense/pallas gather
    K−1 remote models; sparse (static or dynamic) moves one model per
    offset of the (union) permute schedule; centralized is a
    reduce+broadcast (2·(K−1)/K); none moves nothing.  ``A`` may be a
    ``(K, K)`` matrix or a stacked ``(S, K, K)`` schedule.
    """
    A = np.asarray(A)
    K = A.shape[-1]
    if strategy in ("none",):
        return 0
    if strategy in ("sparse", "sparse_host", "mesh_sparse",
                    "sparse_dynamic", "sparse_host_dynamic",
                    "mesh_sparse_dynamic"):
        return _ir_for(A).degree * model_bytes
    if strategy == "centralized":
        return 2 * (K - 1) * model_bytes // K
    return (K - 1) * model_bytes


# ---------------------------------------------------------------------------
# Diffusion steps
# ---------------------------------------------------------------------------

def atc_step(params: PyTree, updates: PyTree, combine: CombineFn) -> PyTree:
    """Adapt-then-Combine (paper eq. 6a-6b): φ = w + u;  w' = A ⊙ φ."""
    phi = jax.tree.map(lambda p, u: p + u, params, updates)
    return combine(phi)


def cta_step(params: PyTree, updates: PyTree, combine: CombineFn) -> PyTree:
    """Combine-then-Adapt variant (consensus-flavored)."""
    mixed = combine(params)
    return jax.tree.map(lambda p, u: p + u, mixed, updates)


# ---------------------------------------------------------------------------
# Theory metrics
# ---------------------------------------------------------------------------

def centroid(params: PyTree) -> PyTree:
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), params)


def disagreement(params: PyTree) -> jax.Array:
    """Network disagreement (Thm 1): (1/K) Σ_k ‖w_k − w_c‖²."""
    leaves = jax.tree.leaves(params)
    K = leaves[0].shape[0]
    total = jnp.zeros((), jnp.float32)
    for x in leaves:
        xc = jnp.mean(x, axis=0, keepdims=True)
        total = total + jnp.sum((x - xc).astype(jnp.float32) ** 2)
    return total / K
