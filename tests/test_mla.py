"""MLA's pieces: YaRN inverse frequencies, and the flash kernel at MLA's
head widths (q and k 192 dims, v 128) with YaRN's softmax scale."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YaRNConfig
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import layers as L
from repro.models.attention import mla_softmax_scale
from repro.models.registry import get_config

DSV2 = YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707)


def _yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse
    frequencies, transcribed line by line."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    inv_freq_mask = 1.0 - np.clip(linear, 0, 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32)
                                          / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


@pytest.mark.parametrize("dim,base,orig", [(64, 10000.0, 4096),
                                           (32, 10000.0, 2048),
                                           (64, 500000.0, 8192)])
def test_yarn_inv_freq_matches_the_formula(dim, base, orig):
    y = YaRNConfig(factor=40.0, original_max_position_embeddings=orig)
    got = np.asarray(L.rope_inv_freq(dim, base, y))
    want = _yarn_inv_freq(dim, base, 40.0, orig, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the fastest dims keep their frequency, the slowest are divided
    plain = np.asarray(L.rope_inv_freq(dim, base))
    assert got[0] == plain[0]
    np.testing.assert_allclose(got[-1], plain[-1] / 40.0, rtol=1e-6)


def test_yarn_angles_and_softmax_scale():
    """cos and sin keep unit scale (mscale = mscale_all_dim), and MLA's
    softmax scale is mscale(40, 0.707)**2 / sqrt(192)."""
    pos = jnp.arange(16)
    cos, sin = L.rope_angles(pos, 64, 10000.0, DSV2)
    ang = np.arange(16)[:, None] * _yarn_inv_freq(64, 10000.0, 40.0, 4096,
                                                  32.0, 1.0)
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=1e-5)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.2608, abs=1e-4)
    cfg = get_config("deepseek-v2-lite-16b")
    assert mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))


def _grads(fn, q, k, v, ct):
    return jax.grad(lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * ct), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_attention_mla_widths_with_scale(dtype, tol):
    """q, k at 192 dims and v at 128 (MLA expanded), YaRN's scale, in
    interpret mode, against ``L.blocked_attention``: forward, dq, dk,
    dv."""
    B, S, H = 1, 256, 2
    scale = 1.5896 / math.sqrt(192)
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (B, S, H, 192), dtype)
    k = jax.random.normal(ks[1], (B, S, H, 192), dtype)
    v = jax.random.normal(ks[2], (B, S, H, 128), dtype)
    ct = jax.random.normal(ks[3], (B, S, H, 128))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=128, block_k=64, interpret=True)

    def blocked(q, k, v):
        return L.blocked_attention(q, k, v, causal=True, block_q=64,
                                   block_k=64, scale=scale)

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        got = kernel(q, k, v)
        want = blocked(*f32)
        g_got = _grads(kernel, q, k, v, ct)
        g_want = _grads(blocked, *f32, ct)
    assert got.shape == (B, S, H, 128)
    err = np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want)))
    assert err < tol * np.max(np.abs(np.asarray(want)))
    for name, g, w in zip("qkv", g_got, g_want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < tol, f"d{name}: max error {err:.3g} of the largest"
