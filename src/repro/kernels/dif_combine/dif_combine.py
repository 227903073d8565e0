"""Pallas TPU kernels for the Dif-MAML outer update: one pass over the
parameter bytes.

Memory-traffic contract (per step, per (K, M) dtype group; P = K·M·itemsize
parameter-set bytes, F = K·M·4 fp32-moment bytes)
================================================================

Unfused (clip → Adam moments → apply → combine as separate HLO), counting
each buffer's HBM round-trips:

  =================  =============================================  =======
  stage              traffic                                        bytes
  =================  =============================================  =======
  global-norm pass   read g                                         1P
  clip scale         read g, write g_c                              2P
  Adam moments       read g_c (×2), mu, nu; write mu, nu            2P + 4F
  update direction   read mu, nu; write u                           1P + 2F
  apply φ = w + u    read w, u; write φ                             3P
  combine A·φ        read φ, write w'                               2P
  =================  =============================================  =======

  total ≈ 11P + 6F  — measured 15.1P on compiled XLA:CPU HLO at f32
  (XLA fuses some of the above; the combine einsum and the moment updates
  stay separate because each has a different output set).

Fused (``fused_combine_update``): everything between the norm pass and the
new launch model is **one kernel** —

  =================  =============================================  =======
  global-norm pass   read g (the clip scale must precede tile 0)    1P
  fused kernel       read w, g, mu, nu; write w', mu, nu            3P + 4F
  =================  =============================================  =======

  total = 4P + 4F: each buffer is read once and written at most once.
  At f32 (F = P) that is 8P vs ~15P measured unfused (0.53×); at bf16
  params/grads with fp32 moments (F = 2P) it is 12 bf16-units vs ~27
  measured (0.44×) — the `outer_update` benchmark row pins both.

Per (K, bm) tile the fused kernel (a) gathers the traced step's combination
matrix from the stacked ``(S, K, K)`` schedule table by one-hot reduction
(no scalar prefetch — runs on both supported JAX lines), (b) applies the
pre-computed per-agent global-norm clip scale, (c) advances the optimizer
moments in fp32 (``repro.optim.optimizers`` scalar math — the same
expressions the HLO path evaluates), and (d) emits the new launch model for
the ATC (``w' = A·(w + u)``), consensus (``w' = A·w + u``) or local
(``w' = w + u``) composition.  ``combine_every`` gating is branch-free:
``A_eff = gate·A_s + (1 − gate)·I``, so skipped steps still advance the
moments while the mix degenerates to the identity.

``dif_combine`` is the original combine-only kernel (paper eq. 6b,
``out[k, m] = Σ_l A[l, k]·φ[l, m]``): grid over M/bm, each (K, bm) φ-tile
read once and mixed into all K output rows by one ``Aᵀ·φ`` contraction —
one pass over the parameter bytes instead of K−1 separate axpy passes,
used by the ``pallas`` combine backend and the ``cta`` pre-mix.

Tiling: bm must be lane-aligned (multiple of 128) so reductions run on the
VPU at full width; K rides the sublane dim (K ≥ 8 tiles exactly at f32).
``interpret=True`` runs the same kernels on CPU for CI parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_KINDS = ("sgd", "momentum", "adam")
_MODES = ("atc", "consensus", "local")


def _combine_kernel(a_ref, phi_ref, out_ref):
    # every agent row of the tile at once: out[k] = Σ_l A[l, k] · phi[l]
    mixed = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), phi_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    out_ref[...] = mixed.astype(out_ref.dtype)


def _check_block(M: int, block_m: int) -> None:
    if block_m < 1 or block_m % 128:
        raise ValueError(
            f"block_m={block_m} must be a positive multiple of the 128-lane "
            f"width (full-width VPU tiles)")
    if M % block_m:
        raise ValueError(
            f"packed feature dim M={M} is not a multiple of "
            f"block_m={block_m}; zero-pad the buffer to the block multiple "
            f"(pack_pytree / the fused tree driver do this) or pick a "
            f"block_m dividing M")


def dif_combine(A: jax.Array, phi: jax.Array, *, block_m: int = 512,
                interpret: bool = False) -> jax.Array:
    """A: (K, K) doubly-stochastic; phi: (K, M).  Returns (K, M)."""
    K, M = phi.shape
    if A.shape != (K, K):
        raise ValueError(
            f"combination matrix shape {A.shape} does not match the "
            f"K={K} stacked agents of phi {phi.shape}; need A of "
            f"shape ({K}, {K})")
    _check_block(M, block_m)
    # the output block spans all K rows: a (1, bm) row block of a (K, M)
    # array breaks the TPU's (8, 128) tiling rule unless K == 1
    return pl.pallas_call(
        _combine_kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((K, K), lambda m: (0, 0)),
            pl.BlockSpec((K, block_m), lambda m: (0, m)),
        ],
        out_specs=pl.BlockSpec((K, block_m), lambda m: (0, m)),
        out_shape=jax.ShapeDtypeStruct((K, M), phi.dtype),
        interpret=interpret,
    )(A, phi)


# ---------------------------------------------------------------------------
# Fused combine-then-update kernel
# ---------------------------------------------------------------------------

def _fused_kernel(tab_ref, sel_ref, ctl_ref, scale_ref, w_ref, g_ref, *rest,
                  mode: str, kind: str, lr: float, b1: float, b2: float,
                  eps: float, weight_decay: float, beta: float):
    from repro.optim import optimizers as om

    w32 = w_ref[...].astype(jnp.float32)                        # (K, bm)
    g32 = (g_ref[...].astype(jnp.float32)
           * scale_ref[...].astype(jnp.float32))                # clip, (K,1)·

    if kind == "adam":
        mu_ref, nu_ref, w_out, mu_out, nu_out = rest
        bc1, bc2 = ctl_ref[0, 1], ctl_ref[0, 2]
        mu = om.adam_mu(mu_ref[...], g32, b1)
        nu = om.adam_nu(nu_ref[...], g32, b2)
        u = om.adam_direction(mu, nu, bc1, bc2, lr=lr, eps=eps,
                              weight_decay=weight_decay, p32=w32)
        mu_out[...] = mu
        nu_out[...] = nu
    elif kind == "momentum":
        vel_ref, w_out, vel_out = rest
        v = om.momentum_velocity(vel_ref[...].astype(jnp.float32), g32, beta)
        u = om.momentum_direction(v, lr=lr)
        vel_out[...] = v.astype(vel_out.dtype)
    else:                                                       # sgd
        (w_out,) = rest
        u = om.sgd_direction(g32, lr=lr)

    if mode == "local":
        new = w32 + u
    else:
        K = w32.shape[0]
        S = tab_ref.shape[0]
        # one-hot gather of the traced step's matrix from the (S, K, K)
        # schedule table: a VPU reduction, no scalar-prefetch grid needed
        sel = sel_ref[0, 0]
        hot = (jax.lax.broadcasted_iota(jnp.int32, (S, 1, 1), 0)
               == sel).astype(jnp.float32)
        A = jnp.sum(tab_ref[...].astype(jnp.float32) * hot, axis=0)  # (K, K)
        # branch-free CommSchedule gating: skipped steps mix with I
        gate = ctl_ref[0, 0]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
               ).astype(jnp.float32)
        A_eff = gate * A + (1.0 - gate) * eye
        phi = w32 + u if mode == "atc" else w32
        # out[k] = Σ_l A_eff[l, k] · phi[l]
        mixed = jax.lax.dot_general(A_eff, phi, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        new = mixed if mode == "atc" else mixed + u
    w_out[...] = new.astype(w_out.dtype)


def fused_combine_update(table: jax.Array, sel: jax.Array, ctl: jax.Array,
                         scale: jax.Array, params: jax.Array,
                         grads: jax.Array, mu: jax.Array | None = None,
                         nu: jax.Array | None = None, *, mode: str = "atc",
                         kind: str = "adam", lr: float, b1: float = 0.9,
                         b2: float = 0.999, eps: float = 1e-8,
                         weight_decay: float = 0.0, beta: float = 0.9,
                         block_m: int = 512, interpret: bool = False):
    """One-pass combine-then-update over a packed (K, M) dtype group.

    Arguments (see module docstring for the traffic contract):

    ``table``  (S, K, K) stacked schedule (S=1 for a static graph); for
               ``mode='local'`` it is unread but must still be (S, K, K).
    ``sel``    (1, 1) int32 — the traced ``step % S`` row index.
    ``ctl``    (1, 3) float32 — ``[gate, bc1, bc2]``: the CommSchedule
               gate (1.0 = mix this step) and the Adam bias corrections
               (ignored for sgd/momentum).
    ``scale``  (K, 1) float32 per-agent global-norm clip scale (ones when
               unclipped).
    ``params``/``grads``  (K, M), any float dtype (one dtype group).
    ``mu``/``nu``  fp32 moment buffers: both for ``kind='adam'``; ``mu`` =
               velocity (param dtype) for ``'momentum'``; neither for
               ``'sgd'``.

    Returns ``(new_params, new_mu, new_nu)`` with ``None`` for absent
    moment buffers.  Zero-padded columns stay zero through the kernel
    (eps > 0 keeps the Adam direction finite at 0/0), so callers may pad
    ragged leaves to the block multiple and slice the pad off.
    """
    if kind not in _KINDS:
        raise ValueError(f"unknown optimizer kind {kind!r}; one of {_KINDS}")
    if mode not in _MODES:
        raise ValueError(f"unknown combine mode {mode!r}; one of {_MODES}")
    K, M = params.shape
    if grads.shape != (K, M):
        raise ValueError(
            f"grads shape {grads.shape} does not match params {params.shape}")
    if table.ndim != 3 or table.shape[1:] != (K, K):
        raise ValueError(
            f"schedule table shape {table.shape} does not match the K={K} "
            f"stacked agents of params {params.shape}; need (S, {K}, {K})")
    _check_block(M, block_m)
    n_mom = {"sgd": 0, "momentum": 1, "adam": 2}[kind]
    moments = [m for m in (mu, nu)[:n_mom]]
    if len([m for m in (mu, nu) if m is not None]) != n_mom:
        raise ValueError(
            f"optimizer kind {kind!r} takes exactly {n_mom} moment "
            f"buffer(s); got mu={'set' if mu is not None else None}, "
            f"nu={'set' if nu is not None else None}")
    for name, m in zip(("mu", "nu"), moments):
        if m.shape != (K, M):
            raise ValueError(
                f"{name} shape {m.shape} does not match params "
                f"{params.shape}")
    if kind == "adam":
        for name, m in zip(("mu", "nu"), moments):
            if m.dtype != jnp.float32:
                raise ValueError(
                    f"adam moment {name} must be float32 (fp32 moments are "
                    f"the fused contract), got {m.dtype}")

    S = table.shape[0]
    grid = (M // block_m,)
    row = lambda m: (0, m)
    fixed = lambda *_: (0,) * 3
    in_specs = [
        pl.BlockSpec((S, K, K), fixed),
        pl.BlockSpec((1, 1), lambda m: (0, 0)),
        pl.BlockSpec((1, 3), lambda m: (0, 0)),
        pl.BlockSpec((K, 1), lambda m: (0, 0)),
        pl.BlockSpec((K, block_m), row),
        pl.BlockSpec((K, block_m), row),
    ] + [pl.BlockSpec((K, block_m), row) for _ in moments]
    out_shape = [jax.ShapeDtypeStruct((K, M), params.dtype)] + [
        jax.ShapeDtypeStruct((K, M), m.dtype) for m in moments]
    out_specs = [pl.BlockSpec((K, block_m), row) for _ in out_shape]

    kernel = functools.partial(_fused_kernel, mode=mode, kind=kind, lr=lr,
                               b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay, beta=beta)
    # each tile is read before it is written, so params and moments update
    # in place: outputs alias inputs 4 (params) and 6.. (moments)
    aliases = {4: 0, **{6 + i: 1 + i for i in range(n_mom)}}
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases=aliases,
        interpret=interpret,
    )(table, sel, ctl, scale, params, grads, *moments)
    outs = list(outs) + [None, None]
    return outs[0], outs[1] if n_mom >= 1 else None, \
        outs[2] if n_mom >= 2 else None
