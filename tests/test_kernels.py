"""Pallas kernels (interpret mode) vs pure-jnp oracles, sweeping shapes
and dtypes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.gram_norm import gram_norm, gram_norm_fused
from repro.kernels.pe_conv_grad import pe_conv_grad_1d, pe_conv_grad_2d


@pytest.mark.parametrize("shape", [(3, 50, 16, 24), (2, 256, 32, 8),
                                   (2, 300, 7, 5), (1, 8, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("has_bias", [False, True])
def test_gram_norm(shape, dtype, has_bias):
    B, T, Di, Do = shape
    rng = np.random.RandomState(sum(shape))
    x = jnp.array(rng.randn(B, T, Di), dtype)
    dy = jnp.array(rng.randn(B, T, Do), dtype)
    got = gram_norm(x, dy, has_bias=has_bias, bt=64, interpret=True)
    want = ref.gram_norm_ref(x, dy, has_bias=has_bias)
    rtol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol)


@pytest.mark.parametrize("shape", [(3, 50, 16, 24), (2, 130, 7, 5),
                                   (1, 8, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("has_bias", [False, True])
def test_gram_norm_fused_kernel_vs_ref(shape, dtype, has_bias):
    """The fused norm+contrib kernel body (interpret mode) against the
    jnp reference that serves as the CPU dispatch of ops.gram_norm_fused
    — both outputs, plus the bias contribution when present."""
    B, T, Di, Do = shape
    rng = np.random.RandomState(sum(shape))
    x = jnp.array(rng.randn(B, T, Di), dtype)
    dy = jnp.array(rng.randn(B, T, Do), dtype)
    w = jnp.array(rng.rand(B), jnp.float32)
    n_k, c_k, cb_k = gram_norm_fused(x, dy, w, has_bias=has_bias, bt=64,
                                     interpret=True)
    n_r, c_r, cb_r = ref.gram_norm_fused_ref(x, dy, w, has_bias=has_bias)
    rtol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(n_k), np.asarray(n_r), rtol=rtol)
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r), rtol=rtol,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cb_k), np.asarray(cb_r),
                               rtol=rtol, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 5, 6, 20, 3), (1, 3, 8, 33, 5),
                                   (4, 2, 2, 9, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pe_conv_grad_1d_kernel(shape, dtype):
    B, C, D, T, K = shape
    rng = np.random.RandomState(sum(shape))
    x = jnp.array(rng.randn(B, C, T), dtype)
    dy = jnp.array(rng.randn(B, D, T - K + 1), dtype)
    got = pe_conv_grad_1d(x, dy, K=K, interpret=True)
    want = ref.pe_conv_grad_1d_ref(x, dy, K)
    rtol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol,
                               atol=1e-2)


@pytest.mark.parametrize("shape", [(2, 3, 4, 10, 3), (1, 2, 6, 8, 2)])
def test_pe_conv_grad_2d_kernel(shape):
    B, C, D, HW, K = shape
    rng = np.random.RandomState(sum(shape))
    x = jnp.array(rng.randn(B, C, HW, HW), jnp.float32)
    dy = jnp.array(rng.randn(B, D, HW - K + 1, HW - K + 1), jnp.float32)
    got = pe_conv_grad_2d(x, dy, KH=K, KW=K, interpret=True)
    want = ref.pe_conv_grad_2d_ref(x, dy, K, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("case", [
    # (B, C, D, H, W, K, P, th): several row tiles with padding, channels
    # over one 128-lane tile and not a multiple of it, a partial block of
    # examples
    (5, 130, 136, 12, 7, 3, 1, 4),
    (3, 5, 4, 9, 6, 5, 2, 3),       # 5x5, padding 2
    (2, 3, 6, 6, 5, 3, 1, 1),       # one row a step
    (3, 4, 5, 8, 8, 3, 0, 2),       # no padding
    # row tiles that do not divide H: the last tile reaches past the image
    (3, 5, 4, 11, 11, 5, 2, 6),     # 5x5 as AlexNet's conv1, one row over
    (2, 3, 4, 7, 6, 3, 1, 3),       # 3x3, two rows over
    (2, 3, 4, 9, 5, 3, 1, 4),       # the last tile holds one real row
    (2, 3, 4, 10, 6, 3, 0, 4),      # the last δy window past the image
], ids=["lanes", "k5", "th1", "valid", "ragged_k5", "ragged_k3",
        "ragged_one_row", "ragged_dy_past"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pe_conv_grad_2d_kernel_tiles(case, dtype):
    """Row tiles, padding, lane tiles and partial blocks against the
    oracle on the zero-padded input; bf16 operands are multiplied exactly,
    so only the sums' f32 rounding separates the two."""
    B, C, D, H, W, K, P, th = case
    rng = np.random.RandomState(sum(case))
    x = jnp.array(rng.randn(B, C, H, W), dtype)
    dy = jnp.array(rng.randn(B, D, H + 2 * P - K + 1, W + 2 * P - K + 1),
                   dtype)
    got = pe_conv_grad_2d(x, dy, KH=K, KW=K, padding=(P, P), th=th,
                          interpret=True)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, 0), (P, P), (P, P)))
    want = ref.pe_conv_grad_2d_ref(xp, dy.astype(jnp.float32), K, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("cfg", [
    # (B, T, S, H, Hkv, hd, causal, bq, bk)
    (2, 64, 64, 4, 2, 16, True, 32, 32),
    (1, 128, 128, 2, 2, 8, True, 64, 32),
    (2, 32, 32, 4, 1, 16, False, 16, 16),
])
def test_flash_attention(cfg):
    B, T, S, H, Hkv, hd, causal, bq, bk = cfg
    rng = np.random.RandomState(sum(cfg))
    q = jnp.array(rng.randn(B, T, H, hd), jnp.float32)
    k = jnp.array(rng.randn(B, S, Hkv, hd), jnp.float32)
    v = jnp.array(rng.randn(B, S, Hkv, hd), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_gram_norm_used_by_ghost(toy_model):
    """ops.gram_norm plugs into the same math the ghost strategy uses."""
    from repro.kernels import ops
    rng = np.random.RandomState(0)
    x = jnp.array(rng.randn(3, 24, 10), jnp.float32)
    dy = jnp.array(rng.randn(3, 24, 6), jnp.float32)
    got = ops.gram_norm(x, dy)
    pe = jnp.einsum("bti,bto->bio", x, dy)
    want = jnp.sum(pe ** 2, axis=(1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)
