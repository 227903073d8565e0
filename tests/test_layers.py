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


@pytest.mark.parametrize("q_chunk,causal,window,S,dv", [
    pytest.param(4, True, None, 16, 8, id="4"),        # 4 chunks
    pytest.param(8, True, None, 16, 8, id="8"),        # 2 chunks
    pytest.param(None, True, None, 16, 8, id="None"),
    pytest.param(4, True, None, 12, 8, id="4-odd-chunks"),
    pytest.param(4, True, 3, 16, 8, id="4-window-below-chunk"),
    pytest.param(4, True, 4, 16, 8, id="4-window-at-chunk"),
    pytest.param(4, True, 6, 16, 8, id="4-window-above-chunk"),
    pytest.param(8, True, 5, 16, 8, id="8-window-below-chunk"),
    pytest.param(8, True, 11, 16, 8, id="8-window-above-chunk"),
    pytest.param(4, True, 5, 20, 8, id="4-window-odd-chunks"),
    pytest.param(4, False, None, 16, 8, id="4-noncausal"),
    pytest.param(8, False, None, 16, 8, id="8-noncausal"),
    pytest.param(4, True, None, 16, 5, id="4-value-width-differs"),   # MLA
])
def test_sdpa_chunk_invariance(q_chunk, causal, window, S, dv):
    q, k, v = [jax.random.normal(jax.random.key(i), (2, S, 3, d))
               for i, d in enumerate((8, 8, dv))]
    full = L.sdpa(q, k, v, 0.35, causal=causal, window=window, q_chunk=None)
    out = L.sdpa(q, k, v, 0.35, causal=causal, window=window,
                 q_chunk=q_chunk)
    np.testing.assert_allclose(out, full, atol=1e-5)


@pytest.mark.parametrize("window,S", [(None, 16), (6, 16), (None, 12)])
def test_sdpa_chunked_hvp_matches_unchunked(window, S):
    """``jax.jvp(jax.grad(loss))``, the product ``maml`` takes, through the
    chunked causal path equals the same product through the full square."""
    qkv = tuple(jax.random.normal(jax.random.key(i), (2, S, 3, 8))
                for i in range(3))
    tangent = tuple(jax.random.normal(jax.random.key(10 + i), (2, S, 3, 8))
                    for i in range(3))

    def hvp(q_chunk):
        def loss(args):
            out = L.sdpa(*args, 0.35, causal=True, window=window,
                         q_chunk=q_chunk)
            return jnp.sum(jnp.sin(out))
        return jax.jvp(jax.grad(loss), (qkv,), (tangent,))

    (g_c, hv_c), (g_f, hv_f) = hvp(4), hvp(None)
    for a, b in zip(g_c + hv_c, g_f + hv_f):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_causal_key_ranges():
    assert L.causal_key_ranges(16, 4) == [(0, 4), (0, 8), (0, 12), (0, 16)]
    # the qwen2 benchmark cell: S = 4096 in chunks of 512
    ranges = L.causal_key_ranges(4096, 512)
    assert len(ranges) == 8 and ranges[-1] == (0, 4096)
    assert sum(hi - lo for lo, hi in ranges) // 512 == 36     # of 8 x 8 tiles
    # the fold pairs chunk i with chunk 7 - i: nc + 1 key blocks a step
    assert {ranges[i][1] + ranges[7 - i][1] for i in range(4)} == {9 * 512}


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
