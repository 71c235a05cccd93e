"""Compressed-exchange codec pieces and their wire accounting."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.distributed import collectives as C


def test_quant_lastdim_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((6, 8, 128)), jnp.float32)
    for bits in (4, 6, 8):
        planes, scale = C._quant_lastdim(x, bits)
        y = C._dequant_lastdim(planes, scale, bits, x.shape)
        step = np.asarray(jnp.max(jnp.abs(x.reshape(6, 8, 4, 32)), -1)
                          / (2 ** (bits - 1) - 1))
        err = np.abs(np.asarray(x - y)).reshape(6, 8, 4, 32).max(-1)
        assert (err <= step + 1e-6).all(), bits


def test_quant_preserves_shape_and_wire_size():
    x = jnp.ones((4, 64), jnp.float32)
    planes, scale = C._quant_lastdim(x, 8)
    assert planes.shape == (4, 2, 8)      # 64 -> 2 groups x 8 planes
    assert scale.shape == (4, 2)
    # wire bytes per param: 8 bits + one f32 scale per 32 values
    assert abs(C.compressed_bytes_per_param(8) - (1.0 + 4 / 32)) < 1e-9


def test_compressible_criteria():
    assert C.compressible(jnp.zeros((128, 128)))
    assert not C.compressible(jnp.zeros((10,)))          # tiny
    assert not C.compressible(jnp.zeros((4096, 31)))     # last dim not /32


def test_error_feedback_converges_unbiased():
    """Repeated compress of a constant with error feedback: mean of the
    decompressed stream -> the true value (the paper-codec lossy analogue)."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    resid = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    n = 24
    for _ in range(n):
        x = g + resid
        planes, scale = C._quant_lastdim(x, 4)
        approx = C._dequant_lastdim(planes, scale, 4, x.shape)
        resid = x - approx
        acc = acc + approx
    err = float(jnp.abs(acc / n - g).max())
    one_shot = float(jnp.abs(
        C._dequant_lastdim(*C._quant_lastdim(g, 4), 4, g.shape) - g).max())
    assert err < one_shot / 3, (err, one_shot)


@pytest.mark.parametrize("bits", [4, 8])
def test_exchange_stats_match_codec_arrays(bits):
    """``exchange_stats``' wire bytes are the bytes of the planes and scales
    the codec really returns for compressible leaves, plus raw leaves."""
    tree = {"w": jnp.zeros((64, 128), jnp.float32),
            "norm": jnp.zeros((10,), jnp.float32)}
    want = 0
    for g in jax.tree.leaves(tree):
        if C.compressible(g):
            out = jax.eval_shape(lambda x: C._quant_lastdim(x, bits), g)
            want += sum(a.size * a.dtype.itemsize for a in out)
        else:
            want += g.size * g.dtype.itemsize
    st = C.exchange_stats(tree, bits)
    assert (st.compressed_leaves, st.raw_leaves) == (1, 1)
    assert st.wire_bytes == want
