"""Flash-attention Pallas kernel vs blockwise reference (interpret mode)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.models.layers import blockwise_attention


def _inputs(B, S, KV, G, D, seed=0, dtype=jnp.float32, Sk=None):
    rng = np.random.default_rng(seed)
    Sk = Sk or S
    q = jnp.asarray(rng.standard_normal((B, S, KV, G, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, KV, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, KV, D)), dtype)
    return q, k, v


def _ref(q, k, v, causal=True, window=0, bq=64, bk=64):
    B, S, KV, G, D = q.shape
    o = blockwise_attention(q.reshape(B, S, KV * G, D), k, v, causal=causal,
                            window=window, q_block=bq, kv_block=bk)
    return o.reshape(B, S, KV, G, D)


@pytest.mark.parametrize("B,S,KV,G,D", [
    (1, 128, 1, 1, 64), (2, 256, 2, 2, 64), (1, 256, 4, 1, 128),
    (1, 512, 2, 4, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(B, S, KV, G, D, causal):
    q, k, v = _inputs(B, S, KV, G, D)
    o_ref = _ref(q, k, v, causal=causal)
    o = flash_attention(q, k, v, causal, 0, 64, 64, True)
    err = float(jnp.abs(o - o_ref).max())
    assert err < 2e-5, err


@pytest.mark.parametrize("window", [32, 64])
def test_sliding_window(window):
    q, k, v = _inputs(1, 256, 2, 2, 64, seed=1)
    o_ref = _ref(q, k, v, causal=True, window=window)
    o = flash_attention(q, k, v, True, window, 64, 64, True)
    assert float(jnp.abs(o - o_ref).max()) < 2e-5


def test_bf16_forward():
    q, k, v = _inputs(1, 128, 2, 2, 64, dtype=jnp.bfloat16)
    o_ref = _ref(q, k, v)
    o = flash_attention(q, k, v, True, 0, 64, 64, True)
    assert float(jnp.abs(o.astype(jnp.float32)
                         - o_ref.astype(jnp.float32)).max()) < 3e-2


@pytest.mark.parametrize("B,S,KV,G,D", [(1, 128, 1, 1, 64), (1, 128, 2, 2, 64)])
def test_gradients_match_reference(B, S, KV, G, D):
    q, k, v = _inputs(B, S, KV, G, D, seed=2)

    def loss_kernel(q, k, v):
        o = flash_attention(q, k, v, True, 0, 64, 64, True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _ref(q, k, v)
        return jnp.sum(o * jnp.cos(o))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "q k v".split()):
        err = float(jnp.abs(a - b).max())
        rel = err / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < 2e-4, (name, err, rel)


def test_bf16_gradients_match_f32_reference():
    """bf16 in and out, as every TPU training step runs the kernel.

    Against the f32 reference on the same rounded inputs, the relative RMS
    gradient error read 1.7e-3 to 2.8e-3 over three shapes and two seeds;
    it is bf16 rounding of the outputs, so it cannot tell whether p and ds
    stay f32 inside the kernel (rounding them to bf16 read 1.7e-3 to
    3.4e-3).  It does catch a wrong term or a dropped group sum.
    """
    q, k, v = (x.astype(jnp.bfloat16) for x in _inputs(1, 128, 2, 2, 64, seed=2))

    def loss_kernel(q, k, v):
        o = flash_attention(q, k, v, True, 0, 64, 64, True).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _ref(q, k, v)
        return jnp.sum(o * jnp.cos(o))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, name in zip(gk, gr, "q k v".split()):
        assert a.dtype == jnp.bfloat16, (name, a.dtype)
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        rel = float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
        assert rel < 5e-3, (name, rel)
