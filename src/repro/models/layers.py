"""Neural layers: norms, RoPE, GQA/MLA attention, MLP, MoE, Mamba2 SSD.

Everything is functional: ``*_specs(cfg)`` builds a Spec pytree,
``*_apply(params, ...)`` runs it.  Attention layers support three modes:
full-sequence (train / prefill), single-token decode against a KV cache,
and sliding-window variants of both.

Logical axes used (mapped to mesh axes in sharding/rules.py):
  'embed'   d_model dims            'ffn'      MLP hidden
  'heads'   attention query heads   'kv_heads' KV heads
  'head_dim'                         'vocab'
  'experts'                          'kv_lora'  MLA latent
  'ssm_head' 'ssm_dim' 'ssm_state'  'layers'   scan stacking
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.init import Spec

PyTree = Any
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ArchConfig, d: int | None = None) -> PyTree:
    d = d or cfg.d_model
    p = {"scale": Spec((d,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        p["bias"] = Spec((d,), ("embed",), "zeros")
    return p


def norm_apply(params: PyTree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * params["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(S: int, d: int) -> np.ndarray:
    pos = np.arange(S)[:, None]
    div = np.exp(np.arange(0, d, 2) * (-np.log(10000.0) / d))
    out = np.zeros((S, d), np.float32)
    out[:, 0::2] = np.sin(pos * div)
    out[:, 1::2] = np.cos(pos * div)
    return out


# ---------------------------------------------------------------------------
# Scaled-dot-product helpers
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, scale):
    """q:(B,S,H,D) k/v:(B,T,H,D) mask:(B,S,T) or (S,T) broadcastable."""
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[..., None, :, :] if mask.ndim == 3 else mask,
                       logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def causal_key_ranges(S: int, q_chunk: int) -> list[tuple[int, int]]:
    """The key range ``[lo, hi)`` that each query chunk of causal
    self-attention over S positions can see.

    Chunk ``i`` holds queries ``i*q_chunk .. (i+1)*q_chunk - 1``; no row of
    it sees a key past its last row.  ``sum(hi - lo) / (S // q_chunk * S)``
    is the share of the logit tiles that :func:`_sdpa_causal_folded`
    computes; a sliding window is left to its mask."""
    return [(0, (i + 1) * q_chunk) for i in range(S // q_chunk)]


def _sdpa_causal_folded(q, k, v, scale, *, window: int | None, q_chunk: int):
    """Causal self-attention that computes only the key blocks each query
    chunk can see (:func:`causal_key_ranges`), in one ``lax.scan``.

    The ranges grow chunk by chunk, so the scan folds them: step ``a`` takes
    chunk ``a`` with chunk ``b = nc-1-a``, whose ranges hold ``nc + 1`` key
    blocks together at every step.  Both are read from one window of the
    keys laid out as ``S-1 .. 0, 0 .. S-1``: chunk ``a``'s range reversed,
    then chunk ``b``'s.  Each block of the window meets the query chunk it
    belongs to, and each chunk's softmax runs over its own blocks.  With an
    odd count the middle chunk is paired with itself, and one copy is
    dropped.  The f32 softmax, its mask and ``NEG_INF`` are those of the
    unfolded path; each step's backward recomputes its logit tiles."""
    B, S, H, D = q.shape
    c = q_chunk
    ranges = causal_key_ranges(S, c)
    nc = len(ranges)
    steps = (nc + 1) // 2
    nb = (ranges[0][1] + ranges[-1][1]) // c          # key blocks a step: nc + 1
    kk = jnp.concatenate([jnp.flip(k, 1), k], axis=1)
    vv = jnp.concatenate([jnp.flip(v, 1), v], axis=1)
    qb = q.reshape(B, nc, c, H, D)
    blk = jnp.arange(nb)
    u = blk[:, None] * c + jnp.arange(c)[None, :]     # (nb, c) window slot

    @jax.checkpoint
    def fold_attn(qa, qb_, a, kk, vv):
        hi_a = (a + 1) * c                            # chunk a's keys: [0, hi_a)
        kw = jax.lax.dynamic_slice_in_dim(kk, S - hi_a, nb * c, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(vv, S - hi_a, nb * c, axis=1)
        seg = blk <= a                                # the block belongs to chunk a
        qt = jnp.where(seg[None, :, None, None, None], qa[:, None], qb_[:, None])
        kpos = jnp.where(seg[:, None], hi_a - 1 - u, u - hi_a)
        qpos = (jnp.where(seg, a, nc - 1 - a)[:, None] * c
                + jnp.arange(c)[None, :])
        mask = kpos[:, None, :] <= qpos[:, :, None]   # (nb, c queries, c keys)
        if window is not None:
            mask = mask & (kpos[:, None, :] > qpos[:, :, None] - window)
        logits = jnp.einsum("btqhd,btkhd->bthqk", qt,
                            kw.reshape(B, nb, c, H, D)).astype(jnp.float32)
        logits = jnp.where(mask[None, :, None], logits * scale, NEG_INF)

        def per_chunk(x, reduce, fill):
            # reduce over chunk a's blocks, then over chunk b's
            in_a = seg.reshape((1, nb) + (1,) * (x.ndim - 2))
            return (reduce(jnp.where(in_a, x, fill), 1, keepdims=True),
                    reduce(jnp.where(in_a, fill, x), 1, keepdims=True))

        in_a = seg[None, :, None, None]
        ma, mb = per_chunk(jnp.max(logits, -1), jnp.max, -jnp.inf)
        m = jax.lax.stop_gradient(jnp.where(in_a, ma, mb))
        e = jnp.exp(logits - m[..., None])
        la, lb = per_chunk(jnp.sum(e, -1), jnp.sum, 0.0)
        probs = (e / jnp.where(in_a, la, lb)[..., None]).astype(v.dtype)
        out = jnp.einsum("bthqk,btkhd->btqhd", probs,
                         vw.reshape(B, nb, c, H, v.shape[-1]),
                         preferred_element_type=jnp.float32)
        oa, ob = per_chunk(out, jnp.sum, 0.0)
        return oa[:, 0].astype(v.dtype), ob[:, 0].astype(v.dtype)

    def body(_, inp):
        qa, qb_, a = inp
        return None, fold_attn(qa, qb_, a, kk, vv)

    xs = (jnp.moveaxis(qb[:, :steps], 1, 0),
          jnp.moveaxis(qb[:, ::-1][:, :steps], 1, 0), jnp.arange(steps))
    _, (out_a, out_b) = jax.lax.scan(body, None, xs)
    out = jnp.concatenate([out_a, out_b[::-1][nc % 2:]], axis=0)   # (nc,B,c,H,D)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def _sdpa_chunked(q, k, v, scale, *, causal: bool, window: int | None,
                  q_chunk: int):
    """Query-chunked attention over all T keys, for non-causal attention
    and cross-lengths: one (q_chunk, T) logit tile at a time inside a
    lax.scan, so the (S, T) square is never live at once.  The tiles still
    go through HBM, unlike those of the Pallas flash kernel
    (kernels/flash_attention), which stay on chip."""
    B, S, H, D = q.shape
    T = k.shape[1]
    nc = S // q_chunk
    qc = jnp.moveaxis(q.reshape(B, nc, q_chunk, H, D), 1, 0)    # (nc,B,c,H,D)
    kpos = jnp.arange(T)[None, :]

    @jax.checkpoint  # backward recomputes the (c, T) logit tile per chunk
    def chunk_attn(qi, ci):
        logits = jnp.einsum("bshd,bthd->bhst", qi, k).astype(jnp.float32) * scale
        qpos = ci * q_chunk + jnp.arange(q_chunk)[:, None]
        mask = jnp.ones((q_chunk, T), bool)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, v)

    def body(_, inp):
        qi, ci = inp                                            # (B,c,H,D), ()
        return None, chunk_attn(qi, ci)

    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def sdpa(q, k, v, scale, *, causal: bool, window: int | None = None,
         q_chunk: int | None = 512):
    """Dispatch: chunked when the query length divides cleanly (causal
    self-attention folded to the visible key blocks), full otherwise
    (short sequences / encoder lengths like 1500)."""
    S, T = q.shape[1], k.shape[1]
    if q_chunk and S > q_chunk and S % q_chunk == 0:
        if causal and T == S:
            return _sdpa_causal_folded(q, k, v, scale, window=window,
                                       q_chunk=q_chunk)
        return _sdpa_chunked(q, k, v, scale, causal=causal, window=window,
                             q_chunk=q_chunk)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    return _sdpa(q, k, v, mask, scale)


def causal_mask(S: int, T: int, offset: int = 0,
                window: int | None = None) -> jax.Array:
    """(S, T) mask: query i (global pos offset+i) may see key j iff j <= pos
    and (pos - j) < window."""
    qpos = offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_specs(cfg: ArchConfig, cross: bool = False) -> PyTree:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": Spec((d, H, hd), ("embed", "heads", "head_dim"), "fan_in"),
        "wk": Spec((d, KV, hd), ("embed", "kv_heads", "head_dim"), "fan_in"),
        "wv": Spec((d, KV, hd), ("embed", "kv_heads", "head_dim"), "fan_in"),
        "wo": Spec((H, hd, d), ("heads", "head_dim", "embed"), "fan_in"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = Spec((H, hd), ("heads", "head_dim"), "zeros")
        p["bk"] = Spec((KV, hd), ("kv_heads", "head_dim"), "zeros")
        p["bv"] = Spec((KV, hd), ("kv_heads", "head_dim"), "zeros")
    return p


def _qkv(params, x, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _expand_kv(k, H):
    KV = k.shape[-2]
    if KV == H:
        return k
    return jnp.repeat(k, H // KV, axis=-2)


def attention_apply(params: PyTree, cfg: ArchConfig, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    kv_x: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention.  x: (B,S,d).  kv_x (B,T,d) for cross-attn."""
    H, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv(params, x, kv_x)
    if cfg.use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    k, v = _expand_kv(k, H), _expand_kv(v, H)
    is_causal = causal and kv_x is None
    out = sdpa(q, k, v, 1.0 / np.sqrt(hd), causal=is_causal,
               window=cfg.sliding_window if is_causal else None,
               q_chunk=cfg.attn_q_chunk)
    return jnp.einsum("bshd,hdo->bso", out, params["wo"])


def attention_decode(params: PyTree, cfg: ArchConfig, x: jax.Array,
                     pos: jax.Array, cache_k: jax.Array, cache_v: jax.Array
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode.  x: (B,1,d); pos: (B,) current position;
    cache_k/v: (B, C, KV, hd) where C = full seq (dense) or window (SWA).
    Returns (out (B,1,d), cache_k, cache_v)."""
    H, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv(params, x)
    C = cache_k.shape[1]
    if cfg.use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    slot = pos % C if cfg.sliding_window else pos               # ring buffer
    bidx = jnp.arange(x.shape[0])
    cache_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    kpos = jnp.arange(C)[None, :]
    if cfg.sliding_window:
        # ring buffer: index r holds global position g, the largest g <= pos
        # with g ≡ r (mod C); valid iff g >= 0 and within the window.
        g = pos[:, None] - ((pos[:, None] - kpos) % C)
        mask = (g >= 0) & (pos[:, None] - g < min(cfg.sliding_window, C))
    else:
        mask = kpos <= pos[:, None]
    # grouped-query attention against the *unexpanded* cache: repeating KV
    # to H heads would materialize an H/KV× copy of the whole cache.
    KV = cache_k.shape[2]
    qg = q[:, 0].reshape(q.shape[0], KV, H // KV, hd)           # (B,KV,G,hd)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, cache_k.astype(x.dtype))
    logits = logits.astype(jnp.float32) / np.sqrt(hd)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, cache_v.astype(x.dtype))
    out = out.reshape(x.shape[0], 1, H, hd)
    return (jnp.einsum("bshd,hdo->bso", out, params["wo"]), cache_k, cache_v)


def cross_attention_decode(params: PyTree, cfg: ArchConfig, x: jax.Array,
                           cross_k: jax.Array, cross_v: jax.Array) -> jax.Array:
    """Decode-time cross attention against fixed encoder keys/values
    (B, T, KV, hd) — no cache update."""
    H, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    kk = _expand_kv(cross_k.astype(x.dtype), H)
    vv = _expand_kv(cross_v.astype(x.dtype), H)
    T = kk.shape[1]
    mask = jnp.ones((1, 1, T), bool)
    out = _sdpa(q, kk, vv, mask, 1.0 / np.sqrt(hd))
    return jnp.einsum("bshd,hdo->bso", out, params["wo"])


def cross_kv(params: PyTree, enc: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder states (B,T,d)."""
    k = jnp.einsum("btd,dhk->bthk", enc, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    return k, v


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2) [arXiv:2405.04434]
# ---------------------------------------------------------------------------

def mla_specs(cfg: ArchConfig) -> PyTree:
    d, H = cfg.d_model, cfg.num_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    return {
        "wq": Spec((d, H, dn + dr), ("embed", "heads", "head_dim"), "fan_in"),
        "w_dkv": Spec((d, r), ("embed", "kv_lora"), "fan_in"),
        "w_kr": Spec((d, dr), ("embed", None), "fan_in"),
        "w_uk": Spec((r, H, dn), ("kv_lora", "heads", "head_dim"), "fan_in"),
        "w_uv": Spec((r, H, dv), ("kv_lora", "heads", "head_dim"), "fan_in"),
        "wo": Spec((H, dv, d), ("heads", "head_dim", "embed"), "fan_in"),
        "kv_norm": {"scale": Spec((r,), ("kv_lora",), "ones")},
    }


def mla_apply(params: PyTree, cfg: ArchConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    c_kv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    c_kv = norm_apply(params["kv_norm"], c_kv)
    k_rope = rope(jnp.einsum("bsd,dk->bsk", x, params["w_kr"])[:, :, None, :],
                  positions, cfg.rope_theta)                     # (B,S,1,dr)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uv"])
    # Train path reduces to standard attention on concatenated
    # (nope ‖ rope) keys — reuses the chunked flash-style sdpa.
    H = q.shape[2]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, dr))], -1)
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    scale = 1.0 / np.sqrt(dn + dr)
    # v head dim may differ from qk dim; pad v for the shared kernel? No —
    # sdpa contracts q·k only; v flows through einsum untouched.
    out = sdpa(q_full, k_full, v, scale, causal=True,
               q_chunk=cfg.attn_q_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def mla_decode(params: PyTree, cfg: ArchConfig, x: jax.Array, pos: jax.Array,
               cache_ckv: jax.Array, cache_kr: jax.Array
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Latent-cache decode with the absorption trick: cache only
    (c_kv: (B,C,r), k_rope: (B,C,dr)) — 576 dims/token instead of H*(dn+dv).
    """
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, pos[:, None], cfg.rope_theta)
    # absorb W_uk into the query:  q_eff = q_nope @ W_uk^T  -> latent space
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])
    c_kv = norm_apply(params["kv_norm"],
                      jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]))
    k_r = rope(jnp.einsum("bsd,dk->bsk", x, params["w_kr"])[:, :, None, :],
               pos[:, None], cfg.rope_theta)[:, :, 0]
    bidx = jnp.arange(x.shape[0])
    cache_ckv = cache_ckv.at[bidx, pos].set(c_kv[:, 0].astype(cache_ckv.dtype))
    cache_kr = cache_kr.at[bidx, pos].set(k_r[:, 0].astype(cache_kr.dtype))
    C = cache_ckv.shape[1]
    mask = (jnp.arange(C)[None, :] <= pos[:, None])[:, None, :]  # (B,1,C)
    scale = 1.0 / np.sqrt(dn + dr)
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, cache_ckv.astype(x.dtype))
              + jnp.einsum("bshk,btk->bhst", q_rope, cache_kr.astype(x.dtype)))
    logits = jnp.where(mask[:, None, :, :],                     # (B,1,1,C)
                       logits.astype(jnp.float32) * scale, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, cache_ckv.astype(x.dtype))
    out = jnp.einsum("bshr,rhk->bshk", out_lat, params["w_uv"])
    return (jnp.einsum("bshk,hkd->bsd", out, params["wo"]),
            cache_ckv, cache_kr)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ArchConfig, d_ff: int | None = None) -> PyTree:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act == "swiglu":
        return {"w1": Spec((d, f), ("embed", "ffn"), "fan_in"),
                "w3": Spec((d, f), ("embed", "ffn"), "fan_in"),
                "w2": Spec((f, d), ("ffn", "embed"), "fan_in")}
    return {"w1": Spec((d, f), ("embed", "ffn"), "fan_in"),
            "b1": Spec((f,), ("ffn",), "zeros"),
            "w2": Spec((f, d), ("ffn", "embed"), "fan_in"),
            "b2": Spec((d,), ("embed",), "zeros")}


def mlp_apply(params: PyTree, x: jax.Array) -> jax.Array:
    if "w3" in params:
        h = jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])
        return h @ params["w2"]
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


# ---------------------------------------------------------------------------
# MoE — sort-based token-choice top-k with per-group capacity
# ---------------------------------------------------------------------------

def moe_specs(cfg: ArchConfig) -> PyTree:
    d, f, E = cfg.d_model, cfg.moe_hidden, cfg.num_experts
    p = {
        "router": Spec((d, E), ("embed", None), "fan_in"),
        "w1": Spec((E, d, f), ("experts", "embed", "ffn"), "fan_in"),
        "w3": Spec((E, d, f), ("experts", "embed", "ffn"), "fan_in"),
        "w2": Spec((E, f, d), ("experts", "ffn", "embed"), "fan_in"),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_specs(cfg, cfg.moe_hidden * cfg.num_shared_experts)
    return p


def _route_group(logits: jax.Array, k: int, E: int, C: int):
    """Per-group routing.  logits: (G, E).  Returns (dispatch_idx (E*C,),
    valid (E*C,), combine_w (E*C,)) where dispatch_idx points into tokens."""
    G = logits.shape[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                      # (G, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    flat_e = top_e.reshape(-1)                                  # (G*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(G), k)
    order = jnp.argsort(flat_e, stable=True)                    # group by expert
    se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
    # position of each routed pair within its expert
    pos_in_e = jnp.arange(G * k) - jnp.searchsorted(se, se, side="left")
    keep = pos_in_e < C
    dest = jnp.where(keep, se * C + pos_in_e, E * C)            # drop slot
    buf_tok = jnp.full((E * C + 1,), G, jnp.int32).at[dest].set(st.astype(jnp.int32))[:-1]
    buf_w = jnp.zeros((E * C + 1,), jnp.float32).at[dest].set(sw)[:-1]
    return buf_tok, buf_w


def moe_apply_sorted(params: PyTree, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Sort/gather dispatch (decode path: S small).  At prefill/train
    lengths the per-group argsort+gather defeats GSPMD's batch sharding —
    measured 80 GiB all-gathers per MoE layer on jamba prefill — so long
    sequences use :func:`moe_apply_einsum` instead."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(1, int(S * k * cfg.moe_capacity_factor / E))
    router_dtype = jnp.float32 if cfg.moe_router_dtype == "float32" else x.dtype

    def group(xg):                                              # (S, d)
        logits = xg.astype(router_dtype) @ params["router"].astype(router_dtype)
        buf_tok, buf_w = _route_group(logits, k, E, C)
        xpad = jnp.concatenate([xg, jnp.zeros((1, d), xg.dtype)], axis=0)
        xe = xpad[buf_tok].reshape(E, C, d)                     # gather
        h = jnp.einsum("ecd,edf->ecf", xe, params["w1"])
        g = jnp.einsum("ecd,edf->ecf", xe, params["w3"])
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, params["w2"])
        ye = ye.reshape(E * C, d) * buf_w[:, None].astype(xg.dtype)
        y = jnp.zeros((S + 1, d), xg.dtype).at[buf_tok].add(ye)[:-1]
        return y

    return jax.vmap(group)(x)


def moe_apply_einsum(params: PyTree, cfg: ArchConfig, x: jax.Array,
                     group_size: int = 2048) -> jax.Array:
    """GShard-style one-hot dispatch/combine einsums over token subgroups.

    Every step is an einsum, so SPMD keeps the batch/group dims sharded
    (unlike sort+gather).  Dispatch overhead: 2·gs·k·E·C·d ≈ 10% of the
    expert GEMMs at gs=2048, cap 1.25.  Identical outputs to the sorted
    path under ample capacity (tested); drop *sets* differ only when over
    capacity (sorted drops by expert-sorted order, this by token order —
    both are valid GShard semantics).
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    gs = min(group_size, S)
    ng = S // gs
    C = max(1, int(gs * k * cfg.moe_capacity_factor / E))
    router_dtype = jnp.float32 if cfg.moe_router_dtype == "float32" else x.dtype

    xg = x.reshape(B, ng, gs, d)
    logits = jnp.einsum("bnsd,de->bnse", xg.astype(router_dtype),
                        params["router"].astype(router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                      # (B,ng,gs,k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # flatten the k choices into the token axis (token-major order)
    oh = jax.nn.one_hot(top_e, E, dtype=jnp.float32)            # (B,ng,gs,k,E)
    ohf = oh.reshape(B, ng, gs * k, E)
    pos = jnp.cumsum(ohf, axis=2) - ohf                         # slot within expert
    pos_sel = jnp.sum(pos * ohf, axis=-1)                       # (B,ng,gs*k)
    keep = (pos_sel < C).astype(jnp.float32)
    slot_oh = jax.nn.one_hot(pos_sel.astype(jnp.int32), C,
                             dtype=jnp.float32)                 # (B,ng,gs*k,C)
    dispatch = jnp.einsum("bnse,bnsc->bnsec", ohf * keep[..., None], slot_oh)
    wf = top_w.reshape(B, ng, gs * k).astype(jnp.float32)
    combine_w = dispatch * wf[..., None, None]                  # (B,ng,gs*k,E,C)
    xrep = jnp.repeat(xg, k, axis=2)                            # (B,ng,gs*k,d)
    xe = jnp.einsum("bnsec,bnsd->bnecd", dispatch.astype(x.dtype), xrep)
    h = jnp.einsum("bnecd,edf->bnecf", xe, params["w1"])
    g = jnp.einsum("bnecd,edf->bnecf", xe, params["w3"])
    ye = jnp.einsum("bnecf,efd->bnecd", jax.nn.silu(h) * g, params["w2"])
    y = jnp.einsum("bnsec,bnecd->bnsd", combine_w.astype(x.dtype), ye)
    # sum the k duplicated choices back per token
    y = y.reshape(B, ng, gs, k, d).sum(axis=3)
    return y.reshape(B, S, d)


def moe_load_balance_loss(params: PyTree, cfg: ArchConfig,
                          x: jax.Array) -> jax.Array:
    """Switch-style router auxiliary loss: E · Σ_e f_e · p_e, where f_e is
    the fraction of tokens whose top-1 choice is expert e and p_e the mean
    router probability.  Minimized (=1) at a uniform distribution —
    production MoE meta-training adds `moe_aux_weight ×` this per MoE layer
    to keep routed experts from collapsing under per-agent task skew.
    (Opt-in: not wired into the baseline loss so §Roofline tables stay
    paper-faithful; see `examples/decentralized_lm.py --moe` usage note.)
    """
    E = cfg.num_experts
    router_dtype = jnp.float32 if cfg.moe_router_dtype == "float32" else x.dtype
    logits = jnp.einsum("bsd,de->bse", x.astype(router_dtype),
                        params["router"].astype(router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)                     # (B,S,E)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    return E * jnp.sum(f * p)


def moe_apply(params: PyTree, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """x: (B, S, d).  Dispatch path per cfg.moe_dispatch:

      'sorted'  sort/gather (training: the one-hot einsums cost ~2× extra
                under backward; and for high-k/small-f MoEs like DeepSeek
                the dispatch einsum alone exceeds the expert GEMMs)
      'einsum'  GShard one-hot dispatch (inference: shards cleanly, no
                batch-replicating gathers — measured −75% FLOPs/dev and
                −91% wire on jamba/mixtral prefill_32k)
      'auto'    einsum iff the dispatch/expert flop ratio (2/3)·gs·k/f < 0.5
                and the length divides the group size
    """
    S = x.shape[1]
    mode = cfg.moe_dispatch
    gs = 2048 if S % 2048 == 0 else (1024 if S % 1024 == 0 else 0)
    if mode == "auto":
        ratio = (2 / 3) * (gs * cfg.experts_per_token) / max(1, cfg.moe_hidden)
        mode = "einsum" if (gs and ratio < 0.5) else "sorted"
    if mode == "einsum" and gs:
        y = moe_apply_einsum(params, cfg, x, group_size=gs)
    else:
        y = moe_apply_sorted(params, cfg, x)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x)
    return y


# ---------------------------------------------------------------------------
# Mamba2 — SSD (state-space duality) chunked scan [arXiv:2405.21060]
# ---------------------------------------------------------------------------

def mamba2_specs(cfg: ArchConfig) -> PyTree:
    d = cfg.d_model
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    cw = cfg.ssm_conv
    return {
        "w_x": Spec((d, H, P), ("embed", "ssm_head", "ssm_dim"), "fan_in"),
        "w_z": Spec((d, H, P), ("embed", "ssm_head", "ssm_dim"), "fan_in"),
        "w_B": Spec((d, G, N), ("embed", None, "ssm_state"), "fan_in"),
        "w_C": Spec((d, G, N), ("embed", None, "ssm_state"), "fan_in"),
        "w_dt": Spec((d, H), ("embed", "ssm_head"), "fan_in"),
        "dt_bias": Spec((H,), ("ssm_head",), "zeros"),
        "A_log": Spec((H,), ("ssm_head",), "zeros"),
        "D": Spec((H,), ("ssm_head",), "ones"),
        "conv_x": Spec((cw, H, P), (None, "ssm_head", "ssm_dim"), "fan_in"),
        "conv_B": Spec((cw, G, N), (None, None, "ssm_state"), "fan_in"),
        "conv_C": Spec((cw, G, N), (None, None, "ssm_state"), "fan_in"),
        "norm": {"scale": Spec((H, P), ("ssm_head", "ssm_dim"), "ones")},
        "w_out": Spec((H, P, d), ("ssm_head", "ssm_dim", "embed"), "fan_in"),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along time.  x: (B, L, *ch); w: (cw, *ch)."""
    cw = w.shape[0]
    pad = jnp.zeros(x.shape[:1] + (cw - 1,) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(cw))
    return jax.nn.silu(out)


def _gated_rmsnorm(scale, x, z, eps=1e-6):
    x = x * jax.nn.silu(z)
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def ssd_scan(x, dt, A, B, C, chunk: int,
              init_state: jax.Array | None = None):
    """Chunked SSD.  x: (B,L,H,P), dt: (B,L,H), A: (H,) (<0), B/C: (B,L,G,N).
    Returns (y (B,L,H,P), final_state (B,H,P,N)).

    jnp analogue of kernels/ssd_scan: a lax.scan over chunks carrying the
    (B,H,P,N) state.  Only ONE chunk's (c,c,H) decay tile is live at a time
    — materializing all chunks at once costs O(L·c·H) extra HBM (measured
    2.8 TiB/device on jamba-398B's 256-head mixers before this layout).
    """
    Bb, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = L // chunk
    rep = H // G
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def to_chunks(a):
        return jnp.moveaxis(a.reshape((Bb, nc, chunk) + a.shape[2:]), 1, 0)

    xs = (to_chunks(x), to_chunks(dt.astype(jnp.float32)),
          to_chunks(B), to_chunks(C))

    @jax.checkpoint   # backward recomputes the (c,c,H) decay tile per chunk
    def body(state, inp):
        xc, dtc, Bg, Cg = inp            # (B,c,H,P) (B,c,H) (B,c,G,N) ...
        Bc = jnp.repeat(Bg, rep, axis=2)                        # (B,c,H,N)
        Cc = jnp.repeat(Cg, rep, axis=2)
        dA = dtc * A                                            # (B,c,H) ≤ 0
        seg = jnp.cumsum(dA, axis=1)
        li = seg[:, :, None, :] - seg[:, None, :, :]            # (B,cq,ck,H)
        # mask before the exp: above the diagonal li > 0 grows with the
        # chunk (exp overflows past ~88 at c=256), and where(mask, inf, 0)
        # still sends inf·0 = NaN back through the exp in the backward
        decay = jnp.exp(jnp.where(causal[None, :, :, None], li, -jnp.inf))
        cb = jnp.einsum("bqhn,bkhn->bqkh", Cc, Bc)
        M = (cb * decay * dtc[:, None, :, :]).astype(x.dtype)
        y = jnp.einsum("bqkh,bkhp->bqhp", M, xc)                # intra-chunk
        y += jnp.exp(seg)[..., None].astype(x.dtype) * jnp.einsum(
            "bqhn,bhpn->bqhp", Cc, state)                       # entering state
        end = seg[:, -1:, :]
        w = (jnp.exp(end - seg) * dtc).astype(x.dtype)          # (B,c,H)
        new_state = (state * jnp.exp(end[:, 0])[..., None, None].astype(x.dtype)
                     + jnp.einsum("bkh,bkhn,bkhp->bhpn", w, Bc, xc))
        return new_state, y

    s0 = (jnp.zeros((Bb, H, P, N), x.dtype) if init_state is None
          else init_state.astype(x.dtype))
    final, ys = jax.lax.scan(body, s0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, L, H, P)
    return y, final


def mamba2_apply(params: PyTree, cfg: ArchConfig, x: jax.Array,
                 use_kernel: bool = False) -> jax.Array:
    """Full-sequence Mamba2 mixer.  x: (B, L, d)."""
    xin = jnp.einsum("bld,dhp->blhp", x, params["w_x"])
    z = jnp.einsum("bld,dhp->blhp", x, params["w_z"])
    Bm = jnp.einsum("bld,dgn->blgn", x, params["w_B"])
    Cm = jnp.einsum("bld,dgn->blgn", x, params["w_C"])
    xin = _causal_conv(xin, params["conv_x"])
    Bm = _causal_conv(Bm, params["conv_B"])
    Cm = _causal_conv(Cm, params["conv_C"])
    dt = jax.nn.softplus(jnp.einsum("bld,dh->blh", x, params["w_dt"])
                         + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    L = x.shape[1]
    chunk = min(cfg.ssm_chunk, L)
    if use_kernel:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, _ = ssd_ops.ssd_scan(xin, dt, A, Bm, Cm, chunk=chunk)
    else:
        y, _ = ssd_scan(xin, dt.astype(jnp.float32), A, Bm, Cm, chunk)
    y = y + xin * params["D"][None, None, :, None]
    y = _gated_rmsnorm(params["norm"]["scale"], y, z)
    return jnp.einsum("blhp,hpd->bld", y, params["w_out"])


def mamba2_decode(params: PyTree, cfg: ArchConfig, x: jax.Array,
                  conv_state: jax.Array, ssm_state: jax.Array
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token recurrent step.  x: (B,1,d);
    conv_state: (B, cw-1, H*P + 2*G*N) flattened channel history;
    ssm_state: (B, H, P, N)."""
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    cw = cfg.ssm_conv
    xin = jnp.einsum("bld,dhp->blhp", x, params["w_x"])[:, 0]   # (B,H,P)
    z = jnp.einsum("bld,dhp->blhp", x, params["w_z"])[:, 0]
    Bm = jnp.einsum("bld,dgn->blgn", x, params["w_B"])[:, 0]
    Cm = jnp.einsum("bld,dgn->blgn", x, params["w_C"])[:, 0]
    Bsz = x.shape[0]
    ch = jnp.concatenate([xin.reshape(Bsz, -1), Bm.reshape(Bsz, -1),
                          Cm.reshape(Bsz, -1)], axis=-1)        # (B, ch)
    hist = jnp.concatenate([conv_state, ch[:, None, :]], axis=1)  # (B,cw,ch)
    wx = params["conv_x"].reshape(cw, -1)
    wB = params["conv_B"].reshape(cw, -1)
    wC = params["conv_C"].reshape(cw, -1)
    wall = jnp.concatenate([wx, wB, wC], axis=-1)               # (cw, ch)
    conved = jax.nn.silu(jnp.einsum("bcw,cw->bw", hist, wall))
    xin = conved[:, : H * P].reshape(Bsz, H, P)
    Bm = conved[:, H * P: H * P + G * N].reshape(Bsz, G, N)
    Cm = conved[:, H * P + G * N:].reshape(Bsz, G, N)
    dt = jax.nn.softplus(jnp.einsum("bld,dh->blh", x, params["w_dt"])[:, 0]
                         + params["dt_bias"])                   # (B,H)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)                            # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    decay = jnp.exp(dt * A)[..., None, None]                    # (B,H,1,1)
    upd = dt[..., None, None] * jnp.einsum("bhn,bhp->bhpn", Bh, xin)
    ssm_state = ssm_state * decay.astype(ssm_state.dtype) + upd.astype(ssm_state.dtype)
    y = jnp.einsum("bhpn,bhn->bhp", ssm_state.astype(x.dtype), Ch)
    y = y + xin * params["D"][None, :, None]
    y = _gated_rmsnorm(params["norm"]["scale"], y, z)
    out = jnp.einsum("bhp,hpd->bd", y, params["w_out"])[:, None, :]
    return out, hist[:, 1:], ssm_state
