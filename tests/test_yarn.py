"""YaRN rotary scaling against the published formula.

``expected_*`` transcribe DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``
and ``yarn_get_mscale`` (modeling_deepseek.py, after arXiv:2309.00071) in
numpy float64; the program computes in float32, so the tolerances are a
few float32 ulps.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnConfig
from repro.configs.registry import get_config
from repro.models.attention import mla_softmax_scale
from repro.models.rope import apply_rope, yarn_inv_freq, yarn_mscale

V2_LITE = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                     beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                     mscale_all_dim=0.707)


def expected_mscale(scale, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def expected_inv_freq(dim, base, y):
    def find_dim(num_rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(find_dim(y.beta_fast)), 0)
    high = min(math.ceil(find_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


@pytest.mark.parametrize("dim,base", [(64, 10000.0), (16, 10000.0),
                                      (128, 500000.0)])
def test_inv_freq_matches_published_formula(dim, base):
    got = np.asarray(yarn_inv_freq(dim, base, V2_LITE), np.float64)
    # float32 ``base ** e`` is exact to ~|e ln base| ulps: 13 at 500000
    np.testing.assert_allclose(got, expected_inv_freq(dim, base, V2_LITE),
                               rtol=2e-6)
    # the fastest slot keeps its frequency, the slowest is divided by 40
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(base ** (-(dim - 2) / dim) / 40,
                                    rel=1e-6)


def test_rotation_matches_published_cos_sin():
    """cos/sin tables as published (``emb = cat(freqs, freqs)``, times
    ``mscale(mscale) / mscale(mscale_all_dim)``), applied rotate-half."""
    y = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                   mscale=1.0, mscale_all_dim=0.707)
    rng = np.random.default_rng(0)
    S, dim = 37, 64
    x = rng.normal(size=(1, S, 2, dim)).astype(np.float32)
    pos = np.arange(S) * 113                      # past 4096
    got = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos)[None],
                                10000.0, yarn=y))
    freqs = np.outer(pos, expected_inv_freq(dim, 10000.0, y))
    emb = np.concatenate([freqs, freqs], -1)
    m = expected_mscale(40.0, 1.0) / expected_mscale(40.0, 0.707)
    cos, sin = np.cos(emb) * m, np.sin(emb) * m
    half = np.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    want = x * cos[None, :, None] + half * sin[None, :, None]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_mscale_and_v2_lite_softmax_scale():
    assert yarn_mscale(40.0, 0.707) == pytest.approx(
        expected_mscale(40.0, 0.707))
    assert yarn_mscale(1.0, 0.707) == 1.0
    a = get_config("deepseek-v2-lite-16b").attention
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(a) == pytest.approx(m * m / math.sqrt(192))
    assert mla_softmax_scale(a) == pytest.approx(0.1147214, rel=1e-6)


def test_without_yarn_rope_is_unchanged():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(5)[None].repeat(2, 0)
    a = apply_rope(x, pos, 10000.0)
    b = apply_rope(x, pos, 10000.0, yarn=None)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
