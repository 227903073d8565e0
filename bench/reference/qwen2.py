"""Plain reference of the Qwen2 decoder (arXiv:2407.10671), in float32.

A block is ``x + attn(rmsnorm(x))`` then ``x + mlp(rmsnorm(x))``.
Attention is grouped-query: q, k, v projections with bias, rotary
embeddings (theta ``rope_theta``, rotate-half layout) on q and k, each KV
head shared by ``num_heads / num_kv_heads`` query heads, causal softmax
with scale ``1/sqrt(head_dim)``, output projection without bias.  The MLP
is SwiGLU: ``(silu(x W1) * (x W3)) W2``.  Attention is computed in blocks
of query rows, one block at a time, each against every key with the later
ones masked out.

Departure from the published model, as the configuration file states:
the head is untied from the embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .ops import Ops, lm_loss, rms_norm, scan_layers

Q_BLOCK = 1024


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (R, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(ops: Ops, cfg: dict, p: dict, x: jax.Array) -> jax.Array:
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = ops.einsum("rsd,dhk->rshk", x, p["wq"]) + p["bq"]
    k = ops.einsum("rsd,dhk->rshk", x, p["wk"]) + p["bk"]
    v = ops.einsum("rsd,dhk->rshk", x, p["wv"]) + p["bv"]
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    R, S = x.shape[:2]
    blk = min(Q_BLOCK, S)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        logits = ops.einsum("rqhd,rkhd->rhqk", qb, k) / np.sqrt(hd)
        qpos = start + jnp.arange(blk)[:, None]
        logits = jnp.where(jnp.arange(S)[None, :] <= qpos, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return ops.einsum("rhqk,rkhd->rqhd", probs, v)

    blocks = jnp.moveaxis(q.reshape(R, S // blk, blk, H, hd), 1, 0)
    out = jax.lax.map(rows, (blocks, jnp.arange(0, S, blk)))
    out = jnp.moveaxis(out, 0, 1).reshape(R, S, H, hd)
    return ops.einsum("rshk,hkd->rsd", out, p["wo"])


def _mlp(ops: Ops, p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(ops.einsum("rsd,df->rsf", x, p["w1"])) \
        * ops.einsum("rsd,df->rsf", x, p["w3"])
    return ops.einsum("rsf,fd->rsd", h, p["w2"])


def loss(ops: Ops, params: dict, tokens: jax.Array, labels: jax.Array,
         cfg: dict) -> jax.Array:
    """Mean next-token cross-entropy of one agent's parameters over
    ``tokens``/``labels`` of shape (rows, seq)."""
    x = params["embed"][tokens].astype(jnp.float32)
    (layers,) = params["segments"][0]

    def block(p, h):
        h = h + _attention(ops, cfg, p["attn"], rms_norm(h, p["norm1"]["scale"]))
        return h + _mlp(ops, p["ffn"], rms_norm(h, p["norm2"]["scale"]))

    return lm_loss(ops, scan_layers(block, x, layers), params, labels)


def forward_flops_per_token(arch: dict, seq: int) -> float:
    """Forward FLOPs a token of a sequence of length ``seq``, by the
    conventions of ``bench/flops.py``: every projection, the MLP and the
    head 2 x (weights of the product); causal attention 2 (seq + 1)
    head_dim heads for q.k and probs.v."""
    d, V, L = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    H, KV, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    proj = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * arch["d_ff"]
    layer = 2 * proj + 2 * (seq + 1) * hd * H
    return float(L * layer + 2 * d * V)
