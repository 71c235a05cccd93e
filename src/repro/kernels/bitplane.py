"""Pallas TPU kernels: delta + bitplane pack / unpack (paper §2.5 + §2.4).

The FPGA compressor's loop-carried delta chain and bit-serial packing are
re-expressed for the TPU VPU:

* the delta becomes a shifted lane-wise subtract,
* variable-length packing becomes a 32x32 bitplane transpose keeping only the
  ``bits`` low planes.  The tile is transposed so that each group of 32
  lanes becomes 32 sublanes; a plane word is then a signed sum of disjoint
  bits over those sublanes.  No reshape splits the lane dimension and no
  reduction runs over unsigned integers (the TPU compiler supports neither),
* decode reconstructs with a log-depth lane prefix sum (the cumulative sum is
  the inverse of the delta chain).

Tiling: codes are processed in (BM, BLOCK) VMEM tiles, BLOCK a multiple of
32 lanes x groups; packed planes live in (BM, BLOCK//32*bits) tiles.  The
kernels work in int32; the uint32 plane words are a free bitcast outside.
BM = 128 makes the in-kernel transposes whole (128, 128) tiles; a row
count above 128 is padded to a multiple of it (``tile_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 32
DEF_BM = 128  # rows per tile


def tile_rows(x: jax.Array, bm: int):
    """(x, rows per tile) for a grid over the leading axis.

    Up to ``bm`` rows make one whole-array tile.  More are padded with zero
    rows to a multiple of ``bm``; the caller slices its result back.
    """
    n = x.shape[0]
    if n <= bm:
        return x, n
    return jnp.pad(x, ((0, -n % bm), (0, 0))), bm


def _delta_lanes(v: jax.Array) -> jax.Array:
    """v[:, k] - v[:, k-1] along lanes, first lane raw (int32, exact)."""
    shifted = jnp.pad(v, ((0, 0), (1, 0)))[:, :-1]
    return v - shifted


def _prefix_sum_lanes(v: jax.Array) -> jax.Array:
    """Log-depth inclusive prefix sum along the lane axis (int32, exact)."""
    n = v.shape[-1]
    k = 1
    while k < n:
        shifted = jnp.pad(v, ((0, 0), (k, 0)))[:, :-k]
        v = v + shifted
        k *= 2
    return v


def _pack_kernel(q_ref, out_ref, *, bits: int, block: int):
    v = q_ref[...]                                    # (BM, BLOCK) int32
    bm = v.shape[0]
    d = _delta_lanes(v)
    if bits < 32:
        d = d & ((1 << bits) - 1)
    # groups of 32 lanes become 32 sublanes of a (G, 32, BM) stack; the
    # plane words are signed sums of disjoint bits (no unsigned reduction)
    g = d.T.reshape(block // GROUP, GROUP, bm)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, GROUP, 1), 1)
    planes = [jnp.sum(((g >> j) & 1) << lane, axis=1, keepdims=True)
              for j in range(bits)]                   # static unroll
    out = jnp.concatenate(planes, axis=1)             # (G, bits, BM)
    out_ref[...] = out.reshape(-1, bm).T              # (BM, G*bits)


def _unpack_kernel(p_ref, out_ref, *, bits: int, block: int):
    p = p_ref[...]                                    # (BM, G*bits) int32
    bm = p.shape[0]
    g = p.T.reshape(block // GROUP, bits, bm)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, GROUP, 1), 1)
    vals = jnp.zeros((block // GROUP, GROUP, bm), jnp.int32)
    for j in range(bits):                             # static unroll
        vals = vals | (((g[:, j:j + 1, :] >> lane) & 1) << j)
    if bits < 32:
        h = 1 << (bits - 1)
        vals = (vals ^ h) - h                         # sign extend
    d = vals.reshape(block, bm).T                     # (BM, BLOCK)
    out_ref[...] = _prefix_sum_lanes(d)


@functools.partial(jax.jit, static_argnames=("bits", "block", "bm", "interpret"))
def pack(q: jax.Array, *, bits: int, block: int, bm: int = DEF_BM,
         interpret: bool = False) -> jax.Array:
    """int32 codes [N, block] -> packed planes uint32 [N, block//32*bits]."""
    n = q.shape[0]
    assert q.shape == (n, block), (q.shape, block)
    pw = block // GROUP * bits
    q, bm = tile_rows(q, bm)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits, block=block),
        grid=(q.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, pw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], pw), jnp.int32),
        interpret=interpret,
    )(q)
    return jax.lax.bitcast_convert_type(out[:n], jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bits", "block", "bm", "interpret"))
def unpack(planes: jax.Array, *, bits: int, block: int, bm: int = DEF_BM,
           interpret: bool = False) -> jax.Array:
    """Packed planes uint32 [N, block//32*bits] -> int32 codes [N, block]."""
    n = planes.shape[0]
    pw = block // GROUP * bits
    assert planes.shape == (n, pw), (planes.shape, pw)
    planes, bm = tile_rows(jax.lax.bitcast_convert_type(planes, jnp.int32), bm)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits, block=block),
        grid=(planes.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, pw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((planes.shape[0], block), jnp.int32),
        interpret=interpret,
    )(planes)[:n]
