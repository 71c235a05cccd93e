"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the bit-exact (or tolerance-specified) reference the kernels
are validated against in ``tests/test_kernels.py`` (interpret mode) and that
XLA falls back to where a kernel is not applicable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import blockcodec as bc


# ---------------------------------------------------------------------------
# delta + bitplane pack / unpack (the paper's codec, TPU block form)
# ---------------------------------------------------------------------------

def pack_ref(q: jax.Array, bits: int) -> jax.Array:
    """int32 codes [N, block] -> packed planes uint32 [N, block//32 * bits].

    Delta along the minor axis (first element raw), truncate to ``bits``
    two's-complement bits, bitplane-transpose each 32-word group.
    """
    n, block = q.shape
    d = bc.delta_encode(q)
    g = d.reshape(n, block // bc.GROUP, bc.GROUP)
    planes = bc.bitplane_pack(g, bits)            # [N, G, bits]
    return planes.reshape(n, -1)


def unpack_ref(planes: jax.Array, bits: int, block: int) -> jax.Array:
    """Inverse of pack_ref -> int32 codes [N, block]."""
    n = planes.shape[0]
    g = planes.reshape(n, block // bc.GROUP, bits)
    d = bc.bitplane_unpack(g, bits).reshape(n, block)
    return bc.delta_decode(d)


# ---------------------------------------------------------------------------
# KV-cache block quantization (packed int8 / int4 + per-row scale markers)
# ---------------------------------------------------------------------------

def kv_quant_ref(x: jax.Array, bits: int = 8):
    """[rows, d] float -> (codes int8 [rows, d or d/2], scale f32 [rows, 1]).

    Symmetric per-row quantization; int4 packs two codes per byte
    (lo nibble = even column).
    """
    x = x.astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # multiply by the constant 1/qmax: a division by a constant is
    # rewritten differently by each compiler, which moves the scale by
    # an ulp and flips codes at rounding ties
    scale = jnp.where(amax > 0, amax * (1.0 / qmax), 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    if bits == 8:
        return q.astype(jnp.int8), scale
    if bits == 4:
        # contiguous nibble interleave: pair columns and weight-sum the
        # innermost axis (lo|hi == lo + 16*hi on disjoint nibbles) —
        # q[..., 0::2] strided slices lower to gathers, breaking bursts
        pairs = (q & 0xF).reshape(*q.shape[:-1], -1, 2)
        packed = pairs[..., 0] | (pairs[..., 1] << 4)
        return packed.astype(jnp.int8), scale
    raise ValueError(bits)


def kv_dequant_ref(codes: jax.Array, scale: jax.Array, bits: int = 8) -> jax.Array:
    codes = codes.astype(jnp.int32)
    if bits == 8:
        q = codes
    elif bits == 4:
        def sext4(v):
            return ((v & 0xF) ^ 0x8) - 0x8
        lo = sext4(codes)
        hi = sext4(codes >> 4)
        q = jnp.stack([lo, hi], axis=-1).reshape(*codes.shape[:-1], -1)
    else:
        raise ValueError(bits)
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Chunked jacobi-1d (read -> execute x T -> write macro-pipeline)
# ---------------------------------------------------------------------------

def jacobi_chunked_ref(x: jax.Array, t_steps: int) -> jax.Array:
    """T jacobi steps on the edge-padded infinite extension of x.

    Contract shared with the Pallas kernel: the input is conceptually
    extended left and right with its edge values *at time 0*, then evolved
    T steps; the n interior cells are returned.  (Influence distance is
    exactly T cells, so padding by T is exact.)
    """
    v = jnp.pad(x.astype(jnp.float32), (t_steps, t_steps), mode="edge")
    for _ in range(t_steps):
        v = (v[:-2] + v[1:-1] + v[2:]) / 3.0   # 'valid' update, shrinks by 2
    return v
