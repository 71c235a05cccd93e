"""Pallas TPU kernels: packed KV-cache block quantization.

KV blocks (positions x head_dim) are the serving-side MARS: atomic (a decode
step reads whole blocks), irredundant (each block stored once), contiguous.
Packing them to int8/int4 with a per-row scale marker cuts the decode memory
roofline term 2-4x.  The scale array is the §4.2.2 metadata analogue.

Kernels:
  * quant:   f32/bf16 [rows, d] -> int8 codes [rows, d(, /2)] + f32 scales
  * dequant: inverse, used on the attention read path.

Tiling: (BM, d) VMEM tiles; d is the head_dim (128-aligned in all assigned
architectures).  Rows beyond one tile are padded to a whole number of
tiles (``bitplane.tile_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitplane import tile_rows

DEF_BM = 128  # rows per tile


def _quant_kernel(x_ref, q_ref, s_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)                  # (BM, D)
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # multiply by the constant 1/qmax: a division by a constant is
    # rewritten differently by each compiler, which moves the scale by
    # an ulp and flips codes at rounding ties
    scale = jnp.where(amax > 0, amax * (1.0 / qmax), 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    if bits == 8:
        q_ref[...] = q.astype(jnp.int8)
    else:  # int4: lo nibble = even column, paired on sublanes (a reshape
        # that split the lane dim would not compile for the TPU)
        t = (q & 0xF).T                                 # (D, BM)
        pairs = t.reshape(t.shape[0] // 2, 2, t.shape[1])
        packed = pairs[:, 0, :] | (pairs[:, 1, :] << 4)
        q_ref[...] = packed.T.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref, *, bits: int):
    codes = q_ref[...].astype(jnp.int32)
    if bits == 8:
        q = codes
    else:
        def sext4(v):
            return ((v & 0xF) ^ 0x8) - 0x8
        lo = sext4(codes)
        hi = sext4(codes >> 4)
        q = jnp.stack([lo, hi], axis=-1).reshape(codes.shape[0], -1)
    x_ref[...] = q.astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def kv_quant(x: jax.Array, *, bits: int = 8, bm: int = DEF_BM,
             interpret: bool = False):
    """[rows, d] float -> (codes int8, scales f32 [rows, 1])."""
    rows, d = x.shape
    assert bits == 8 or d % 2 == 0
    cd = d if bits == 8 else d // 2
    x, bm = tile_rows(x, bm)
    padded = x.shape[0]
    codes, scales = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=(padded // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, cd), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, cd), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return codes[:rows], scales[:rows]


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def kv_dequant(codes: jax.Array, scales: jax.Array, *, bits: int = 8,
               bm: int = DEF_BM, interpret: bool = False) -> jax.Array:
    rows, cd = codes.shape
    d = cd if bits == 8 else cd * 2
    codes, bm = tile_rows(codes, bm)
    scales, _ = tile_rows(scales, bm)
    padded = codes.shape[0]
    return pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits),
        grid=(padded // bm,),
        in_specs=[
            pl.BlockSpec((bm, cd), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        interpret=interpret,
    )(codes, scales)[:rows]
