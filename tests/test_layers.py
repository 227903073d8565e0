"""Layer-level unit and property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.init import materialize


def _cfg(**kw):
    cfg = get_config("qwen2-7b").reduced()
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def test_rmsnorm_unit_scale():
    p = {"scale": jnp.ones(8)}
    x = jax.random.normal(jax.random.key(0), (2, 3, 8)) * 5
    y = L.norm_apply(p, x)
    ms = jnp.mean(y.astype(jnp.float32) ** 2, axis=-1)
    np.testing.assert_allclose(ms, 1.0, rtol=1e-3)


def test_layernorm_zero_mean_unit_var():
    p = {"scale": jnp.ones(8), "bias": jnp.zeros(8)}
    x = jax.random.normal(jax.random.key(0), (4, 8)) * 3 + 2
    y = L.norm_apply(p, x).astype(jnp.float32)
    np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-4)
    np.testing.assert_allclose(jnp.var(y, -1), 1.0, rtol=1e-2)


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 4, 9, 14, 20])
def test_rope_preserves_norm(seed):
    x = jax.random.normal(jax.random.key(seed), (1, 6, 2, 16))
    pos = jnp.arange(6)[None]
    y = L.rope(x, pos, 10_000.0)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-4)


def test_rope_relative_position_property():
    """⟨rope(q,i), rope(k,j)⟩ depends only on i−j."""
    d = 32
    q = jax.random.normal(jax.random.key(0), (1, 1, 1, d))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, d))

    def dot_at(i, j):
        qi = L.rope(q, jnp.array([[i]]), 1e4)
        kj = L.rope(k, jnp.array([[j]]), 1e4)
        return float(jnp.sum(qi * kj))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
    assert dot_at(0, 0) == pytest.approx(dot_at(100, 100), rel=1e-4)


def test_rope_position_zero_is_identity():
    x = jax.random.normal(jax.random.key(0), (1, 1, 2, 16))
    y = L.rope(x, jnp.zeros((1, 1)), 1e4)
    np.testing.assert_allclose(y, x, atol=1e-6)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def test_causal_mask_blocks_future():
    cfg = _cfg(attn_q_chunk=None, use_rope=False)
    params = materialize(L.attention_specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model))
    pos = jnp.arange(8)[None]
    y1 = L.attention_apply(params, cfg, x, pos, causal=True)
    # perturb the LAST token only: earlier outputs must not change
    x2 = x.at[:, -1].add(1.0)
    y2 = L.attention_apply(params, cfg, x2, pos, causal=True)
    np.testing.assert_allclose(y1[:, :-1], y2[:, :-1], atol=1e-5)
    assert float(jnp.max(jnp.abs(y1[:, -1] - y2[:, -1]))) > 1e-4


def test_sliding_window_limits_receptive_field():
    cfg = _cfg(attn_q_chunk=None, use_rope=False, sliding_window=2)
    params = materialize(L.attention_specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model))
    pos = jnp.arange(8)[None]
    y1 = L.attention_apply(params, cfg, x, pos, causal=True)
    x2 = x.at[:, 0].add(10.0)     # outside the window of position 7
    y2 = L.attention_apply(params, cfg, x2, pos, causal=True)
    np.testing.assert_allclose(y1[:, -1], y2[:, -1], atol=1e-4)


def test_gqa_expand_matches_mha_when_equal_heads():
    k = jax.random.normal(jax.random.key(0), (1, 4, 2, 8))
    assert L._expand_kv(k, 2) is k
    ke = L._expand_kv(k, 6)
    assert ke.shape == (1, 4, 6, 8)
    np.testing.assert_array_equal(ke[:, :, 0], ke[:, :, 2])


@pytest.mark.parametrize("q_chunk", [4, 8, None])
def test_sdpa_chunk_invariance(q_chunk):
    q, k, v = [jax.random.normal(jax.random.key(i), (2, 16, 3, 8))
               for i in range(3)]
    full = L.sdpa(q, k, v, 0.35, causal=True, q_chunk=None)
    out = L.sdpa(q, k, v, 0.35, causal=True, q_chunk=q_chunk)
    np.testing.assert_allclose(out, full, atol=1e-5)


# ---------------------------------------------------------------------------
# mamba2 building blocks
# ---------------------------------------------------------------------------

def test_causal_conv_is_causal():
    x = jax.random.normal(jax.random.key(0), (1, 10, 2, 4))
    w = jax.random.normal(jax.random.key(1), (3, 2, 4))
    y1 = L._causal_conv(x, w)
    x2 = x.at[:, 5].add(1.0)
    y2 = L._causal_conv(x2, w)
    np.testing.assert_allclose(y1[:, :5], y2[:, :5], atol=1e-6)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_ssd_scan_chunk_invariance(chunk):
    B, Lq, H, P, N = 1, 16, 2, 4, 8
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (B, Lq, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, Lq, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bm = jax.random.normal(ks[3], (B, Lq, H, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, Lq, H, N)) * 0.5
    y1, s1 = L.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = L.ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(y1, y2, atol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4)


def test_ssd_scan_grads_finite_at_published_chunk():
    """mamba2-130m's chunk of 256 with its init decay (A = -1, dt ≈ 0.7):
    above the diagonal the segment sums reach exp(+180), past f32's range.
    The masked entries must not send inf·0 = NaN back through the exp."""
    B, Lq, H, P, N = 1, 256, 2, 4, 8
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (B, Lq, H, P))
    dt = jnp.full((B, Lq, H), 0.7)
    A = -jnp.ones((H,))
    Bm = jax.random.normal(ks[1], (B, Lq, H, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, Lq, H, N)) * 0.5

    def loss(x, dt, A):
        y, s = L.ssd_scan(x, dt, A, Bm, Cm, chunk=256)
        return jnp.sum(y ** 2) + jnp.sum(s ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, dt, A)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


def test_mla_latent_dim_bottleneck():
    """MLA's KV path must flow through the rank-r latent."""
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    specs = L.mla_specs(cfg)
    assert specs["w_dkv"].shape == (cfg.d_model, cfg.kv_lora_rank)
    assert specs["w_uk"].shape[0] == cfg.kv_lora_rank
    assert specs["w_uv"].shape[0] == cfg.kv_lora_rank
