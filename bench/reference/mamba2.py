"""Plain reference of the Mamba-2 language model (arXiv:2405.21060).

Written from the paper, in float32, with the SSD layer in its quadratic
"masked attention" form (paper section 3, eq. 16): for each head,

    y_t = sum_{s <= t} (C_t . B_s) * exp(sum_{r=s+1..t} dt_r A) * dt_s x_s
          + D x_t

which is the same map the chunked scan computes, by another algorithm.
A block is ``x + mixer(rmsnorm(x))`` with no MLP; the mixer projects
x, z, B, C and dt from its input, runs a causal depthwise convolution
(width ``ssm_conv``) and SiLU over x, B and C, dt = softplus(dt + dt_bias),
A = -exp(A_log), then the SSD layer, the gate ``y * silu(z)``, an RMS norm
and the output projection.

Departures from the published model, all of them the model as this
repository builds it and states it in the configuration file:

* the head is untied from the embedding;
* the convolution has no bias;
* the gated RMS norm runs over each head's ``ssm_head_dim`` channels, where
  the published model (one group) normalizes all ``d_inner`` channels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ops import Ops, lm_loss, rms_norm, scan_layers

Q_BLOCK = 256


def _conv_silu(x: jax.Array, w: jax.Array) -> jax.Array:
    """Causal depthwise convolution over time, then SiLU.  x: (R, L, ...),
    w: (cw, ...)."""
    cw, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (cw - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    out = sum(xp[:, i:i + L] * w[i] for i in range(cw))
    return jax.nn.silu(out)


def _ssd(ops: Ops, x, dt, A, B, C):
    """x: (R,L,H,P), dt: (R,L,H), A: (H,), B/C: (R,L,G,N) -> (R,L,H,P).

    Computed in blocks of ``Q_BLOCK`` output positions, one block at a time,
    each against every input position with the later ones masked out; the
    backward pass recomputes a block's (R, Q_BLOCK, L, H) scores."""
    H, G = x.shape[2], B.shape[2]
    B = jnp.repeat(B, H // G, axis=2)
    C = jnp.repeat(C, H // G, axis=2)
    cs = jnp.cumsum(dt * A, axis=1)                         # (R,L,H)
    R, L = x.shape[:2]
    blk = min(Q_BLOCK, L)

    @jax.checkpoint
    def rows(args):
        cb, csb, start = args                               # (R,blk,H,N) ...
        seg = csb[:, :, None, :] - cs[:, None, :, :]         # (R,t,s,H)
        causal = (jnp.arange(L)[None, :] <= start + jnp.arange(blk)[:, None])
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        scores = ops.einsum("rthn,rshn->rtsh", cb, B) * decay * dt[:, None]
        return ops.einsum("rtsh,rshp->rthp", scores, x)

    def blocks(a):
        return jnp.moveaxis(a.reshape((R, L // blk, blk) + a.shape[2:]), 1, 0)

    out = jax.lax.map(rows, (blocks(C), blocks(cs), jnp.arange(0, L, blk)))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape)


def _mixer(ops: Ops, p: dict, x: jax.Array) -> jax.Array:
    xin = _conv_silu(ops.einsum("rld,dhp->rlhp", x, p["w_x"]), p["conv_x"])
    z = ops.einsum("rld,dhp->rlhp", x, p["w_z"])
    B = _conv_silu(ops.einsum("rld,dgn->rlgn", x, p["w_B"]), p["conv_B"])
    C = _conv_silu(ops.einsum("rld,dgn->rlgn", x, p["w_C"]), p["conv_C"])
    dt = jax.nn.softplus(ops.einsum("rld,dh->rlh", x, p["w_dt"])
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = _ssd(ops, xin, dt, A, B, C) + xin * p["D"][:, None]
    y = rms_norm(y * jax.nn.silu(z), p["norm"]["scale"])
    return ops.einsum("rlhp,hpd->rld", y, p["w_out"])


def loss(ops: Ops, params: dict, tokens: jax.Array, labels: jax.Array,
         cfg: dict) -> jax.Array:
    """Mean next-token cross-entropy of one agent's parameters over
    ``tokens``/``labels`` of shape (rows, seq)."""
    x = params["embed"][tokens].astype(jnp.float32)
    (layers,) = params["segments"][0]

    def block(p, h):
        return h + _mixer(ops, p["mamba"], rms_norm(h, p["norm1"]["scale"]))

    return lm_loss(ops, scan_layers(block, x, layers), params, labels)


def forward_flops_per_token(arch: dict, seq: int) -> float:
    """Forward FLOPs a token of a sequence of length ``seq``, by the
    conventions of ``bench/flops.py``:

    * every projection and the head: 2 x (weights of the product);
    * the depthwise convolution: 2 x width x channels;
    * the SSD layer in its chunked form (chunk c = min(ssm_chunk, seq)),
      per head: (c + 1)(N + P) for C.B and the masked product with x inside
      the chunk (each a causal triangle), and 4NP for reading and writing
      the state carried between chunks.
    """
    d, V, L = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    H = arch["ssm_expand"] * d // arch["ssm_head_dim"]
    P, N, G = arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_groups"]
    c = min(arch["ssm_chunk"], seq)
    proj = 2 * d * H * P + 2 * d * G * N + d * H + H * P * d
    conv = arch["ssm_conv"] * (H * P + 2 * G * N)
    ssd = H * ((c + 1) * (N + P) + 4 * N * P)
    layer = 2 * proj + 2 * conv + ssd
    return float(L * layer + 2 * d * V)
