"""Model assembly: decoder LMs, MoE, hybrid (Jamba-style), enc-dec (Whisper),
VLM (Llama-3.2-vision-style) — all from one segment/period abstraction.

A model is a list of **segments**; each segment scans ``n`` repeats of a
**period** (a short list of heterogeneous blocks).  ``lax.scan`` over the
stacked per-period parameters keeps the HLO size O(period), not O(depth) —
essential for 100-layer models compiled on a 512-device mesh.

  dense LM      [Segment(n=L,  period=(attn+mlp,))]
  mixtral       [Segment(n=56, period=(attn+moe,))]
  deepseek      [Segment(n=1, period=(mla+mlp,)), Segment(n=26, period=(mla+moe,))]
  mamba2        [Segment(n=24, period=(mamba,))]
  jamba         [Segment(n=9,  period=(attn+mlp, mamba+moe, mamba+mlp, mamba+moe,
                                       mamba+mlp, mamba+moe, mamba+mlp, mamba+moe))]
  llama-vision  [Segment(n=20, period=(self+mlp ×4, cross+mlp))]
  whisper       encoder [Segment(n=32, period=(enc,))] +
                decoder [Segment(n=32, period=(self+cross+mlp,))]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.init import Spec, materialize, stack_specs

PyTree = Any


@dataclasses.dataclass(frozen=True)
class BlockDesc:
    mixer: str          # attn | attn_nc (non-causal) | mla | mamba | cross
    ffn: str            # dense | moe | none


@dataclasses.dataclass(frozen=True)
class Segment:
    n: int
    period: tuple[BlockDesc, ...]


# ---------------------------------------------------------------------------
# Block specs / apply / decode
# ---------------------------------------------------------------------------

def block_specs(cfg: ArchConfig, desc: BlockDesc) -> PyTree:
    p: dict[str, Any] = {"norm1": L.norm_specs(cfg)}
    if desc.mixer in ("attn", "attn_nc"):
        p["attn"] = L.attention_specs(cfg)
    elif desc.mixer == "mla":
        p["mla"] = L.mla_specs(cfg)
    elif desc.mixer == "mamba":
        p["mamba"] = L.mamba2_specs(cfg)
    elif desc.mixer == "cross":
        p["cross"] = L.attention_specs(cfg, cross=True)
        p["gate"] = Spec((), (), "zeros")       # llama-3.2 gated cross-attn
    if desc.ffn != "none":
        p["norm2"] = L.norm_specs(cfg)
        p["ffn"] = L.moe_specs(cfg) if desc.ffn == "moe" else L.mlp_specs(cfg)
    return p


def block_apply(cfg: ArchConfig, desc: BlockDesc, p: PyTree, x: jax.Array,
                positions: jax.Array, aux: dict[str, jax.Array]) -> jax.Array:
    # each sublayer (norm, op, residual) under one name, for profiles
    with jax.named_scope("dif.model.mixer"):
        h = L.norm_apply(p["norm1"], x)
        if desc.mixer == "attn":
            x = x + L.attention_apply(p["attn"], cfg, h, positions,
                                      causal=True)
        elif desc.mixer == "attn_nc":
            x = x + L.attention_apply(p["attn"], cfg, h, positions,
                                      causal=False)
        elif desc.mixer == "mla":
            x = x + L.mla_apply(p["mla"], cfg, h, positions)
        elif desc.mixer == "mamba":
            x = x + L.mamba2_apply(p["mamba"], cfg, h)
        elif desc.mixer == "cross":
            y = L.attention_apply(p["cross"], cfg, h, positions, causal=False,
                                  kv_x=aux["enc"])
            x = x + jnp.tanh(p["gate"]) * y
    if desc.ffn != "none":
        with jax.named_scope("dif.model.ffn"):
            h = L.norm_apply(p["norm2"], x)
            out = (L.moe_apply(p["ffn"], cfg, h) if desc.ffn == "moe"
                   else L.mlp_apply(p["ffn"], h))
            x = x + out
    return x


def block_cache_specs(cfg: ArchConfig, desc: BlockDesc, batch: int,
                      cache_len: int) -> PyTree:
    """Logical (shape, axes) Spec tree for this block's decode state."""
    if desc.mixer in ("attn", "attn_nc"):
        C = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        kv = Spec((batch, C, cfg.num_kv_heads, cfg.head_dim),
                  ("batch", "seq", "kv_heads", "head_dim"), "zeros")
        return {"k": kv, "v": kv}
    if desc.mixer == "mla":
        return {"ckv": Spec((batch, cache_len, cfg.kv_lora_rank),
                            ("batch", "seq", "kv_lora"), "zeros"),
                "kr": Spec((batch, cache_len, cfg.qk_rope_dim),
                           ("batch", "seq", None), "zeros")}
    if desc.mixer == "mamba":
        H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
        ch = H * P + 2 * G * N
        return {"conv": Spec((batch, cfg.ssm_conv - 1, ch),
                             ("batch", None, None), "zeros"),
                "ssm": Spec((batch, H, P, N),
                            ("batch", "ssm_head", "ssm_dim", "ssm_state"), "zeros")}
    if desc.mixer == "cross":
        T = cfg.num_patches or cfg.encoder_frames
        kv = Spec((batch, T, cfg.num_kv_heads, cfg.head_dim),
                  ("batch", None, "kv_heads", "head_dim"), "zeros")
        return {"ck": kv, "cv": kv}
    return {}


def block_decode(cfg: ArchConfig, desc: BlockDesc, p: PyTree, cache: PyTree,
                 x: jax.Array, pos: jax.Array) -> tuple[jax.Array, PyTree]:
    h = L.norm_apply(p["norm1"], x)
    if desc.mixer in ("attn", "attn_nc"):
        y, ck, cv = L.attention_decode(p["attn"], cfg, h, pos,
                                       cache["k"], cache["v"])
        x, cache = x + y, {"k": ck, "v": cv}
    elif desc.mixer == "mla":
        y, ckv, kr = L.mla_decode(p["mla"], cfg, h, pos,
                                  cache["ckv"], cache["kr"])
        x, cache = x + y, {"ckv": ckv, "kr": kr}
    elif desc.mixer == "mamba":
        y, conv, ssm = L.mamba2_decode(p["mamba"], cfg, h,
                                       cache["conv"], cache["ssm"])
        x, cache = x + y, {"conv": conv, "ssm": ssm}
    elif desc.mixer == "cross":
        y = L.cross_attention_decode(p["cross"], cfg, h,
                                     cache["ck"], cache["cv"])
        x = x + jnp.tanh(p["gate"]) * y
    if desc.ffn != "none":
        h = L.norm_apply(p["norm2"], x)
        out = (L.moe_apply(p["ffn"], cfg, h) if desc.ffn == "moe"
               else L.mlp_apply(p["ffn"], h))
        x = x + out
    return x, cache


# ---------------------------------------------------------------------------
# Segment plans per architecture family
# ---------------------------------------------------------------------------

def segment_plan(cfg: ArchConfig) -> list[Segment]:
    t = cfg.arch_type
    if t == "ssm":
        return [Segment(cfg.num_layers, (BlockDesc("mamba", "none"),))]
    if t == "hybrid":
        per = [BlockDesc("attn", "dense")]
        for i in range(1, cfg.attn_every):
            ffn = "moe" if (cfg.num_experts and i % cfg.moe_every == cfg.moe_offset) else "dense"
            per.append(BlockDesc("mamba", ffn))
        return [Segment(cfg.num_layers // cfg.attn_every, tuple(per))]
    if t == "vlm":
        k = cfg.cross_attn_every
        per = tuple([BlockDesc("attn", "dense")] * (k - 1)
                    + [BlockDesc("cross", "dense")])
        return [Segment(cfg.num_layers // k, per)]
    if t == "moe" and cfg.use_mla:  # deepseek
        segs = []
        if cfg.first_dense_layers:
            segs.append(Segment(cfg.first_dense_layers,
                                (BlockDesc("mla", "dense"),)))
        segs.append(Segment(cfg.num_layers - cfg.first_dense_layers,
                            (BlockDesc("mla", "moe"),)))
        return segs
    if t == "moe":
        return [Segment(cfg.num_layers, (BlockDesc("attn", "moe"),))]
    # dense / audio decoder
    return [Segment(cfg.num_layers, (BlockDesc("attn", "dense"),))]


def decoder_cross_plan(cfg: ArchConfig) -> list[Segment]:
    """Whisper decoder: self-attn + cross-attn + mlp per layer."""
    return [Segment(cfg.num_layers,
                    (BlockDesc("attn", "none"), BlockDesc("cross", "dense")))]


def encoder_plan(cfg: ArchConfig) -> list[Segment]:
    return [Segment(cfg.encoder_layers, (BlockDesc("attn_nc", "dense"),))]


# ---------------------------------------------------------------------------
# Segment-level specs / apply / decode (lax.scan over stacked period params)
# ---------------------------------------------------------------------------

def segment_specs(cfg: ArchConfig, seg: Segment) -> PyTree:
    return tuple(stack_specs(block_specs(cfg, d), seg.n) for d in seg.period)


def segment_apply(cfg: ArchConfig, seg: Segment, params: PyTree, x: jax.Array,
                  positions: jax.Array, aux: dict,
                  constrain=None) -> jax.Array:
    constrain = constrain or (lambda h: h)

    # remat_span groups `span` periods per checkpoint region: the scan then
    # saves only every span-th residual (1/span of activation HBM) and the
    # backward re-runs at most span periods.
    span = max(1, min(cfg.remat_span, seg.n))
    while seg.n % span:
        span -= 1

    def body(h, group_params):
        h = constrain(h)   # pin batch sharding inside the scan: the layer
        for i in range(span):                           # residual stack
            layer_params = (group_params if span == 1 else
                            jax.tree.map(lambda a: a[i], group_params))
            for desc, p in zip(seg.period, layer_params):
                h = block_apply(cfg, desc, p, h, positions, aux)
        return constrain(h), None

    if cfg.remat:
        body = jax.checkpoint(body)
    if span > 1:
        params = jax.tree.map(
            lambda a: a.reshape((seg.n // span, span) + a.shape[1:]), params)
    x, _ = jax.lax.scan(body, x, params)
    return x


def segment_cache_specs(cfg: ArchConfig, seg: Segment, batch: int,
                        cache_len: int) -> PyTree:
    return tuple(stack_specs(block_cache_specs(cfg, d, batch, cache_len), seg.n)
                 for d in seg.period)


def segment_decode(cfg: ArchConfig, seg: Segment, params: PyTree,
                   cache: PyTree, x: jax.Array, pos: jax.Array
                   ) -> tuple[jax.Array, PyTree]:
    def body(h, inp):
        layer_params, layer_cache = inp
        new_cache = []
        for desc, p, c in zip(seg.period, layer_params, layer_cache):
            h, nc = block_decode(cfg, desc, p, c, h, pos)
            new_cache.append(nc)
        return h, tuple(new_cache)

    x, new_cache = jax.lax.scan(body, x, (params, cache))
    return x, new_cache


# ---------------------------------------------------------------------------
# Full models
# ---------------------------------------------------------------------------

class Model:
    """Bundles specs + pure functions for one architecture."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.plan = segment_plan(cfg)
        self.is_encdec = cfg.arch_type == "audio"
        if self.is_encdec:
            self.plan = decoder_cross_plan(cfg)
            self.enc_plan = encoder_plan(cfg)
        # Optional NamedSharding for (batch, seq, d_model) activations.
        # Set by launch/steps.py for pod-placement archs: without it GSPMD
        # follows the TP params and silently replicates the batch dim over
        # the data axis (measured 16× per-device FLOPs on mixtral/jamba).
        self.act_sharding = None

    def _constrain(self, x: jax.Array) -> jax.Array:
        if self.act_sharding is not None:
            return jax.lax.with_sharding_constraint(x, self.act_sharding)
        return x

    # -- specs ---------------------------------------------------------------
    def specs(self) -> PyTree:
        cfg = self.cfg
        V, d = cfg.padded_vocab, cfg.d_model
        p: dict[str, Any] = {
            "embed": Spec((V, d), ("vocab", "embed"), "embed", 0.02),
            "final_norm": L.norm_specs(cfg),
            "head": Spec((d, V), ("embed", "vocab"), "fan_in"),
            "segments": [segment_specs(cfg, s) for s in self.plan],
        }
        if self.is_encdec:
            p["encoder"] = {
                "segments": [segment_specs(cfg, s) for s in self.enc_plan],
                "final_norm": L.norm_specs(cfg),
            }
        if cfg.arch_type == "vlm":
            # stub projector: patch embeddings (already d_model) -> d_model
            p["vision_proj"] = Spec((d, d), ("embed", None), "fan_in")
        return p

    def init(self, key: jax.Array, dtype=jnp.float32) -> PyTree:
        return materialize(self.specs(), key, dtype)

    # -- encoder (whisper stub frontend: frames are precomputed embeddings) --
    def encode(self, params: PyTree, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        F = frames.shape[1]
        pos_tab = jnp.asarray(L.sinusoidal_positions(F, cfg.d_model),
                              frames.dtype)
        x = frames + pos_tab[None]
        positions = jnp.arange(F)[None]
        for seg, sp in zip(self.enc_plan, params["encoder"]["segments"]):
            x = segment_apply(cfg, seg, sp, x, positions, {},
                              constrain=self._constrain)
        return L.norm_apply(params["encoder"]["final_norm"], x)

    def _aux(self, params: PyTree, batch: dict) -> dict:
        cfg = self.cfg
        if self.is_encdec:
            return {"enc": self.encode(params, batch["encoder_frames"])}
        if cfg.arch_type == "vlm":
            return {"enc": batch["image_patches"] @ params["vision_proj"]}
        return {}

    # -- forward -------------------------------------------------------------
    def forward(self, params: PyTree, batch: dict) -> jax.Array:
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = params["embed"][tokens]
        if not cfg.use_rope:  # absolute sinusoidal positions (whisper decoder)
            x = x + jnp.asarray(L.sinusoidal_positions(S, cfg.d_model),
                                x.dtype)[None]
        positions = jnp.arange(S)[None]
        aux = self._aux(params, batch)
        x = self._constrain(x)
        for seg, sp in zip(self.plan, params["segments"]):
            x = segment_apply(cfg, seg, sp, x, positions, aux,
                              constrain=self._constrain)
        with jax.named_scope("dif.model.head"):
            x = L.norm_apply(params["final_norm"], x)
            return x @ params["head"]

    def loss_fn(self, params: PyTree, batch: dict) -> jax.Array:
        logits = self.forward(params, batch)
        with jax.named_scope("dif.model.head"):
            logits = logits.astype(jnp.float32)
            labels = batch["labels"]
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(logz - gold)

    # -- decode --------------------------------------------------------------
    def cache_specs(self, batch: int, cache_len: int) -> PyTree:
        return [segment_cache_specs(self.cfg, s, batch, cache_len)
                for s in self.plan]

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16,
                   params: PyTree | None = None,
                   enc: jax.Array | None = None) -> PyTree:
        """Zero caches; if (params, enc) given, prefill cross-attn K/V."""
        cache = materialize(self.cache_specs(batch, cache_len),
                            jax.random.key(0), dtype)
        if enc is not None and params is not None:
            cache = self._fill_cross(params, cache, enc, dtype)
        return cache

    def _fill_cross(self, params, cache, enc, dtype):
        for si, (seg, sp) in enumerate(zip(self.plan, params["segments"])):
            for pi, desc in enumerate(seg.period):
                if desc.mixer != "cross":
                    continue
                def per_layer(p):
                    k, v = L.cross_kv(p["cross"], enc)
                    return k.astype(dtype), v.astype(dtype)
                ks, vs = jax.vmap(per_layer)(sp[pi])
                cache[si][pi]["ck"] = ks
                cache[si][pi]["cv"] = vs
        return cache

    def decode_step(self, params: PyTree, cache: PyTree, token: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, PyTree]:
        """One decode step.  token: (B,1) int32, pos: (B,) int32.
        Returns (logits (B,1,V), new cache)."""
        cfg = self.cfg
        x = params["embed"][token]
        if not cfg.use_rope:
            pe = _sinusoid_at(pos, cfg.d_model).astype(x.dtype)
            x = x + pe[:, None, :]
        new_cache = []
        for seg, sp, sc in zip(self.plan, params["segments"], cache):
            x, nc = segment_decode(cfg, seg, sp, sc, x, pos)
            new_cache.append(nc)
        x = L.norm_apply(params["final_norm"], x)
        return x @ params["head"], new_cache


def _sinusoid_at(pos: jax.Array, d: int) -> jax.Array:
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-np.log(10000.0) / d))
    ang = pos[:, None].astype(jnp.float32) * div
    out = jnp.zeros((pos.shape[0], d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return out


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
