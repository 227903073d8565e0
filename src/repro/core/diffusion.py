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

Every matrix backend takes a ``(K, K)`` matrix, a stacked ``(S, K, K)``
schedule (step ``i`` mixes with ``A[i % S]``) or a
:class:`repro.core.topology.ScheduleIR`.

``dense``        einsum against the full K×K matrix (a stacked schedule's
                 matrix is picked with the traced step).  Under pjit with
                 the agent axis sharded over a mesh axis, XLA lowers this
                 to all-gather + local reduction: O(K·|w|) collective
                 bytes.  Paper-faithful baseline semantics for arbitrary
                 graphs.
``sparse_host_dynamic``
                 host-roll lowering of the matrix's ScheduleIR: one
                 weighted ``jnp.roll`` per offset in the period's offset
                 *union*, with the per-step weight rows gathered by the
                 traced step index.  Exact for every matrix and schedule
                 (inactive offsets carry zero weights that step); under
                 GSPMD each roll stays a collective-permute of one shard,
                 so sparse graphs keep O(deg·|w|) wire instead of the
                 O(K·|w|) the dense einsum pays.
``sparse_dynamic``
                 the same IR lowered to ``lax.ppermute`` rounds, to be
                 called *inside* an existing shard_map/manual context
                 (one-agent-per-shard); the permute set is fixed across
                 steps — only the weight gather sees the step — so one
                 jitted program serves the whole schedule.
``mesh_sparse_dynamic``
                 production sparse combine: ``sparse_dynamic`` wrapped in
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

``mesh_sparse_dynamic`` requires the agent axis it shard_maps over to hold
exactly one agent per shard (extent == K).  Two mesh generations satisfy
this (the full contract lives in ``launch/mesh.py``):

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

The ppermute backends (``sparse_dynamic``/``mesh_sparse_dynamic``) take a
``combine_dtype`` — the dtype φ travels in on the collective-permute
rounds:

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
``"float32"``    full-width wire (the default): φ is promoted to f32 for
                 the rounds and the mix accumulates in f32 (the escape
                 hatch when bf16 parity is in question).

:func:`resolve_combine_dtype` owns the default: the wire is bf16 exactly
when the outer (param/grad) dtype is bf16, and ``--combine-dtype f32``
overrides it.  :func:`wire_elem_bytes` maps the resolved name to the
per-element wire bytes the budget checks (``tree_shard_bytes`` /
``agent_combine_check`` / ``AGENT_MESH_BUDGETS``) must size against.

Backend selection
=================

:func:`select_backend` is the one place a combine request becomes a
registered backend; :func:`make_combine` builds through it, and callers
that need the resolved name (``launch/steps.py::build_train``) ask it.
A registered name resolves to itself.  ``"sparse"`` asks for the ppermute
lowering wherever the step runs: ``mesh_sparse_dynamic`` on a mesh with a
first-class ``agent`` axis, ``sparse_host_dynamic`` everywhere else (on a
legacy mesh with the agents on ``data``, GSPMD keeps each roll a
collective-permute and leaves the TP axes alone, where a partial-manual
shard_map whose in_specs omit the auto axes would all-gather TP shards at
entry).  ``"auto"`` picks by topology, mesh and accelerator, reading the
matrix's ScheduleIR:

  1. K == 1                                  → ``none``
  2. sparse offset union (deg < K−1) on a live mesh whose ``axis_name``
     extent equals K                         → ``mesh_sparse_dynamic``
  3. sparse offset union, otherwise          → ``sparse_host_dynamic``
  4. a static dense ``(K, K)`` matrix, no mesh, TPU backend → ``pallas``
     (on a live mesh the packed layout would break leaf shardings,
     so dense-einsum keeps the GSPMD lowering; a stacked schedule with
     a dense union keeps the step-indexed einsum)
  5. otherwise                               → ``dense``
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import topology

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


# ---------------------------------------------------------------------------
# ScheduleIR sparse combines: fixed ppermute rounds over the period's offset
# union, per-step weights gathered with the traced step index (a static
# matrix is the period-1 schedule)
# ---------------------------------------------------------------------------

def _ir_for(A) -> topology.ScheduleIR:
    """Accept a ScheduleIR, a (K, K) matrix, or a stacked (S, K, K)
    schedule and return the ScheduleIR lowering."""
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
                                wire_dtype: str = "float32") -> CombineFn:
    """``lax.ppermute`` lowering of a dynamic schedule, to be called
    *inside* shard_map with the agent axis one-agent-per-shard over
    ``axis_name``.

    The permute set is the period's offset union — fixed across steps, so
    the whole schedule compiles to one program; only the weight gather
    (two scalar loads per round from the (S, ·, K) tables) sees the step.
    Wire bytes per combine: D · wire_elem_bytes · |w_local| with D = deg
    of the union, vs (K−1)/K · K · |w_local| for the all-gather XLA emits
    for the dense einsum.  ``wire_dtype`` follows the module-docstring
    wire-format contract."""
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
                                     wire_dtype: str = "float32"
                                     ) -> CombineFn:
    """Production sparse combine: shard_map over the agent mesh axis with
    the :func:`make_sparse_dynamic_combine` rounds; the step index rides in
    replicated.  The agent axis is manual; all other axes (e.g. 'model'
    tensor parallelism) stay auto.  Partial-manual shard_map must run
    under jit.

    ``in_specs``: pytree of PartitionSpecs matching phi's *actual*
    shardings (agent dim on ``axis_name`` plus whatever TP axes each leaf
    carries).  Omitting the TP axes would make shard_map all-gather every
    TP-sharded parameter at entry — measured +77% step wire bytes on
    qwen2-1.5b — so callers must pass the real specs for TP-sharded
    trees."""
    from jax.sharding import PartitionSpec as _P

    inner = make_sparse_dynamic_combine(ir, axis_name, wire_dtype=wire_dtype)
    specs = in_specs if in_specs is not None else _P(axis_name)
    # Every axis the specs mention must be manual; any remaining mesh axis
    # stays auto (partial-manual mode — fine on TPU, but XLA:CPU cannot
    # partition it, so CPU callers should pass specs covering their axes).
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


def _matrix(A) -> jax.Array:
    """``A`` for the backends that contract the matrix itself: a ScheduleIR
    gives back its ``(K, K)`` matrix, or its ``(S, K, K)`` stack when
    ``S > 1``."""
    if isinstance(A, topology.ScheduleIR):
        A = A.matrix_at(0) if A.period == 1 else A.stacked()
    return jnp.asarray(A)


@register_backend("dense")
def _build_dense(*, A, **_ctx) -> CombineFn:
    Aj = _matrix(A)
    if Aj.ndim == 3:
        return _stacked(Aj, dense_combine)
    return _stepless(functools.partial(dense_combine, Aj))


def _check_agent_extent(mesh, axis_name: str, K: int) -> None:
    extent = compat.mesh_axis_sizes(mesh).get(axis_name)
    if extent != K:
        raise ValueError(
            f"mesh_sparse_dynamic needs one agent per shard: axis "
            f"{axis_name!r} has extent {extent} but the schedule is over "
            f"K={K} agents. Use 'sparse_host_dynamic' when the agent axis "
            f"spans multiple mesh axes (e.g. multi-pod data placement).")


@register_backend("sparse_host_dynamic", emits_permutes=True)
def _build_sparse_host_dynamic(*, A, **_ctx) -> CombineFn:
    return make_sparse_host_dynamic_combine(_ir_for(A))


@register_backend("sparse_dynamic", needs_axis_name=True,
                  emits_permutes=True)
def _build_sparse_dynamic(*, A, axis_name, combine_dtype, **_ctx
                          ) -> CombineFn:
    return make_sparse_dynamic_combine(_ir_for(A), axis_name,
                                       wire_dtype=combine_dtype)


@register_backend("mesh_sparse_dynamic", needs_mesh=True,
                  needs_axis_name=True, emits_permutes=True)
def _build_mesh_sparse_dynamic(*, A, mesh, axis_name, in_specs=None,
                               combine_dtype, **_ctx) -> CombineFn:
    ir = _ir_for(A)
    _check_agent_extent(mesh, axis_name, ir.K)
    return make_mesh_sparse_dynamic_combine(ir, mesh, axis_name,
                                            in_specs=in_specs,
                                            wire_dtype=combine_dtype)


@register_backend("pallas")
def _build_pallas(*, A, block_m=512, interpret=None, **_ctx) -> CombineFn:
    Aj = _matrix(A)
    if Aj.ndim == 3:
        return _stacked(Aj, functools.partial(_pallas_apply, block_m=block_m,
                                              interpret=interpret))
    return _stepless(make_pallas_combine(Aj, block_m=block_m,
                                         interpret=interpret))


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


def select_backend(A, *, mesh=None, axis_name: str | None = None,
                   request: str = "auto") -> str:
    """The registered backend a combine ``request`` lowers to (module
    docstring, "Backend selection"): a registered name is itself;
    ``'sparse'`` and ``'auto'`` resolve from what the call can observe —
    the matrix's ScheduleIR degree, the mesh and the agent axis's extent.

    A mesh with a first-class ``agent`` axis announces the agent extent
    itself: when ``axis_name`` is not given it defaults to ``'agent'`` on
    such meshes, so 2D ``(agent, model)`` production meshes route sparse
    topologies to the shard_mapped backend without the caller having to
    know which mesh generation it is on."""
    if request in _BACKENDS:
        return request
    agent_mesh = "agent" in getattr(mesh, "axis_names", ())
    if request == "sparse":
        return "mesh_sparse_dynamic" if agent_mesh else "sparse_host_dynamic"
    if request != "auto":
        raise ValueError(
            f"unknown combine strategy {request!r}; registered: "
            f"{combine_backends()}, or 'auto' / 'sparse'")
    if agent_mesh and axis_name is None:
        axis_name = "agent"
    if A is None:
        return "dense"
    ir = _ir_for(A)
    if ir.K == 1:
        return "none"
    if ir.degree < ir.K - 1:          # strictly fewer rounds than a gather
        if (mesh is not None and axis_name is not None
                and compat.mesh_axis_sizes(mesh).get(axis_name) == ir.K):
            return "mesh_sparse_dynamic"
        return "sparse_host_dynamic"
    if mesh is None and np.ndim(A) == 2 and jax.default_backend() == "tpu":
        # fused one-pass dense reduction of a static matrix; only off-mesh
        # — pack_pytree's concatenate would destroy leaf shardings on a
        # live mesh, forcing an all-gather of every TP shard
        return "pallas"
    return "dense"


def make_combine(strategy: str, A=None, axis_name: str | None = None, *,
                 mesh=None, in_specs: PyTree | None = None,
                 block_m: int = 512, interpret: bool | None = None,
                 combine_dtype: str = "float32") -> CombineFn:
    """Single entry point: build a combine fn from a request — any
    :func:`combine_backends` name, ``'sparse'`` or ``'auto'`` — resolved
    by :func:`select_backend`.

    ``A`` may be one ``(K, K)`` matrix, a stacked ``(S, K, K)`` schedule
    (see :class:`repro.core.topology.TopologySchedule`), or a pre-lowered
    :class:`repro.core.topology.ScheduleIR`.  Sparse graphs are served at
    O(deg·|w|) wire by the ``*_dynamic`` ppermute family (fixed rounds,
    a stacked schedule's weights gathered with the step passed to
    ``combine(phi, step)``) and at O(K·|w|) by 'dense'/'pallas'.

    ``combine_dtype``: wire format for the ppermute backends (see the
    module docstring) — 'bfloat16' | 'float32'.  Backends without a wire
    (dense, pallas, host rolls, …) ignore it.
    """
    if combine_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"combine_dtype {combine_dtype!r} is not a supported wire "
            f"format; pick one of {sorted(WIRE_DTYPES)}")
    name = select_backend(A, mesh=mesh, axis_name=axis_name,
                          request=strategy)
    backend = _BACKENDS[name]
    if backend.needs_matrix:
        assert A is not None, f"{name!r} combine needs a matrix A"
    if backend.needs_axis_name:
        assert axis_name is not None, f"{name!r} combine needs axis_name"
    if backend.needs_mesh:
        assert mesh is not None, f"{name!r} combine needs a mesh"
    return backend.build(A=A, axis_name=axis_name, mesh=mesh,
                         in_specs=in_specs, block_m=block_m,
                         interpret=interpret, combine_dtype=combine_dtype)


def combine_wire_bytes(A: np.ndarray, strategy: str, model_bytes: int) -> int:
    """Per-step collective-byte model for a backend (benchmark reporting).

    ``model_bytes``: size of one agent's launch model.  dense/pallas gather
    K−1 remote models; the ppermute backends move one model per offset of
    the (union) permute schedule; centralized is a reduce+broadcast
    (2·(K−1)/K); none moves nothing.  ``A`` may be a ``(K, K)`` matrix or
    a stacked ``(S, K, K)`` schedule.
    """
    A = np.asarray(A)
    K = A.shape[-1]
    if strategy == "none":
        return 0
    if strategy in ("sparse_dynamic", "sparse_host_dynamic",
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
