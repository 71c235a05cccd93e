"""Compression codecs: faithful §2.5 stream + TPU block codec."""
import re

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

from repro.core import blockcodec as bc
from repro.core import compression as comp
from repro.core import packing
from repro.distributed import collectives as C
from repro.kernels import ref
from repro.models import layers


@settings(max_examples=30, deadline=None)
@given(st.sampled_from([12, 18, 24, 28, 32, 64]),
       st.lists(st.integers(0, 2**31), min_size=1, max_size=120),
       st.booleans())
def test_faithful_roundtrip_and_cost(nbits, vals, smooth):
    mask = (1 << nbits) - 1
    words = np.array(vals, dtype=np.uint64)
    if smooth:
        words = np.cumsum(words % 7, dtype=np.uint64)
    words &= np.uint64(mask)
    w = comp.BitWriter()
    comp.compress_words(words, nbits, w)
    r = comp.BitReader(w.to_words(32), w.bit_length, 32)
    out = comp.decompress_words(r, len(words), nbits)
    assert np.array_equal(out, words)
    # vectorized size model is bit-exact vs the real stream
    assert comp.compressed_cost_bits(words, nbits) == w.bit_length


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 40), min_size=1, max_size=6))
def test_markers_seek_any_mars(sizes):
    rng = np.random.default_rng(0)
    arrs = [rng.integers(0, 1 << 18, size=k, dtype=np.uint64) for k in sizes]
    s = comp.compress_mars_stream(arrs, 18)
    assert len(s.markers) == len(arrs)
    for i in np.random.default_rng(1).permutation(len(arrs)):
        assert np.array_equal(comp.decompress_mars(s, int(i)), arrs[int(i)])


def test_smooth_data_compresses():
    """Jacobi-like smooth data must beat the padded baseline (Fig. 11)."""
    x = np.cumsum(np.random.default_rng(0).uniform(-1e-4, 1e-4, 50_000)) + 0.5
    words = comp.quantize_fixed(x, 18)
    bits = comp.compressed_cost_bits(words, 18)
    r = packing.compression_ratios(len(x), 18, bits)
    assert r.ratio_with_padding > 2.0
    assert r.true_ratio > 1.1


def test_fixed_point_quantization_error():
    x = np.random.default_rng(0).uniform(-1, 1, 1000)
    w = comp.quantize_fixed(x, 18)
    y = comp.dequantize_fixed(w, 18)
    assert np.abs(x - y).max() <= 2 ** -(18 - 2) + 1e-12


# --- block codec (TPU form) -------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.sampled_from([1, 3, 7, 8, 13, 17, 32]), st.integers(1, 5))
def test_bitplane_roundtrip(b, nrows):
    rng = np.random.default_rng(b)
    lo = -(1 << (b - 1)) if b < 32 else -(2**31)
    hi = (1 << (b - 1)) - 1 if b < 32 else 2**31 - 1
    v = rng.integers(lo, hi + 1, size=(nrows, 2, 32)).astype(np.int32)
    planes = bc.bitplane_pack(jnp.asarray(v), b)
    out = bc.bitplane_unpack(planes, b)
    assert np.array_equal(np.asarray(out), v)


@settings(max_examples=15, deadline=None)
@given(st.sampled_from([4, 6, 8, 12]), st.booleans())
def test_block_codec_error_bound(bits, delta):
    rng = np.random.default_rng(bits)
    x = rng.standard_normal(4 * 256).astype(np.float32)
    cfg = bc.BlockCodecConfig(bits=bits, block=256, delta=delta)
    planes, scale = bc.compress(jnp.asarray(x), cfg)
    y = np.asarray(bc.decompress(planes, scale, cfg)).reshape(-1)
    qbits = bits - 1 if delta else bits
    step = np.abs(x).reshape(-1, 256).max(axis=1) / (2 ** (qbits - 1) - 1)
    err = np.abs(x - y).reshape(-1, 256).max(axis=1)
    assert (err <= step + 1e-6).all()


def test_block_codec_wire_size():
    cfg = bc.BlockCodecConfig(bits=8, block=256, delta=False)
    assert bc.compressed_bytes(1024, cfg) == 4 * (256 // 32) * 8 * 4 + 4 * 4
    # ~4x smaller than f32
    assert bc.compressed_bytes(1024, cfg) < 1024 * 4 / 3.8


def test_varwidth_encoder_adapts():
    rng = np.random.default_rng(0)
    smooth = np.cumsum(rng.integers(-2, 3, 4096)).astype(np.int32)
    rough = rng.integers(-2**20, 2**20, 4096).astype(np.int32)
    bs, ws = bc.encode_varwidth(smooth, 256)
    br, wr = bc.encode_varwidth(rough, 256)
    assert bs < br
    assert ws.max() <= wr.max()


# --- compiled access pattern of the packers ---------------------------------

def _pack_case(name, bits):
    """(fn, input specs) of one quantize/pack function at a small shape."""
    spec = jax.ShapeDtypeStruct
    rows, d, n, block = 64, 64, 256, 32
    cd = d if bits == 8 else d // 2
    return {
        "layers._quant_rows": (lambda x: layers._quant_rows(x, bits),
                               [spec((4, 16, d), jnp.float32)]),
        "layers._dequant_rows": (
            lambda c, s: layers._dequant_rows(c, s, bits),
            [spec((4, 16, cd), jnp.int8), spec((4, 16, 1), jnp.float32)]),
        "ref.kv_quant_ref": (lambda x: ref.kv_quant_ref(x, bits),
                             [spec((rows, d), jnp.float32)]),
        "ref.kv_dequant_ref": (
            lambda c, s: ref.kv_dequant_ref(c, s, bits),
            [spec((rows, cd), jnp.int8), spec((rows, 1), jnp.float32)]),
        "ref.pack_ref": (lambda q: ref.pack_ref(q, bits),
                         [spec((n, block), jnp.int32)]),
        "ref.unpack_ref": (lambda p: ref.unpack_ref(p, bits, block),
                           [spec((n, block // 32 * bits), jnp.uint32)]),
        "collectives._quant_lastdim": (
            lambda x: C._quant_lastdim(x, bits),
            [spec((8, 128), jnp.float32)]),
        "collectives._dequant_lastdim": (
            lambda p, s: C._dequant_lastdim(p, s, bits, (8, 128)),
            [spec((8, 4, bits), jnp.uint32), spec((8, 4), jnp.float32)]),
    }[name]


_PACKERS = ("layers._quant_rows", "layers._dequant_rows",
            "ref.kv_quant_ref", "ref.kv_dequant_ref", "ref.pack_ref",
            "ref.unpack_ref", "collectives._quant_lastdim",
            "collectives._dequant_lastdim")


def _innermost_stride(slice_attr):
    """Stride of the last dimension of an HLO ``slice={[a:b:s], ...}``."""
    dims = re.findall(r"\[\d+:\d+(?::(\d+))?\]", slice_attr)
    return int(dims[-1] or 1) if dims else 1


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("name", _PACKERS)
def test_pack_lowers_without_gather(name, bits):
    """Every quantize/pack path reads and writes whole contiguous rows: the
    compiled program has no gather and no slice striding its innermost
    dimension (a ``q[..., 0::2]`` nibble pack compiles to gathers)."""
    fn, specs = _pack_case(name, bits)
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    gathers = re.findall(r"(?<![\w-])gather\(", hlo)
    strided = [m for m in re.findall(r"slice=\{([^}]*)\}", hlo)
               if _innermost_stride(m) > 1]
    assert not gathers and not strided, (len(gathers), strided)
