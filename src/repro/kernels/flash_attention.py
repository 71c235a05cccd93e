"""Pallas TPU flash attention (GQA, causal / sliding-window).

The pure-XLA blockwise attention spills its S^2-shaped intermediates to
HBM.  This kernel keeps the s/p blocks in VMEM (the paper's insight applied
to attention: contiguous blocks + on-chip reuse = bandwidth saved), reducing
attention HBM traffic to the q/k/v/o I/O.

Public layout: q (B, S, KV, G, D); k/v (B, S, KV, D) — grouped GQA, no
repeated KV materialization.  The kernels run head-major, q (B, KV, G, S, D)
and k/v (B, KV, S, D), so every block's last two dims are (seq block, D):
(multiple of 8, multiple of 128) or whole, as the TPU lowering requires.

Scores are computed transposed, sT = k q^T of shape (bk, bq): keys on
sublanes, queries on lanes.  Every per-query statistic (running max, sum,
lse, delta) is then a lane-dense (1, bq) row, and lse/delta are stored as
(B, KV, G, 1, S) arrays whose blocks (1, bq) are whole on the sublane dim.
Scores take q/k in their own dtype with f32 accumulation; p and ds stay
f32 in every matmul they enter, as in the XLA blockwise path.

Grid (B, KV, G, nq, nk): nk innermost, online-softmax state (m, l, acc)
carried in VMEM scratch across the nk sweep.  Forward + backward (dq, dk,
dv) kernels with jax.custom_vjp; backward recomputes p per block from the
saved lse — the flash-2 scheme.  Validated in interpret mode against the
blockwise reference in tests/test_flash_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _mask_t(qi, ki, bq: int, bk: int, causal: bool, window: int):
    """(bk, bq) validity of key row vs query column."""
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    m = jnp.ones((bk, bq), bool)
    if causal:
        m &= q_pos >= k_pos
    if window > 0:
        m &= (q_pos - k_pos) < window
    return m


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bq: int, bk: int, nk: int, causal: bool, window: int,
                scale: float):
    qi, ki = pl.program_id(3), pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, 0]                                 # (bq, D)
    k = k_ref[0, 0]                                    # (bk, D)
    v = v_ref[0, 0]
    s = _dot(k, q, _NT) * scale                        # (bk, bq)
    s = jnp.where(_mask_t(qi, ki, bq, bk, causal, window), s, NEG_INF)

    m_prev = m_scr[...]                                # (1, bq)
    m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=0, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _dot(v.astype(F32), p, _TN)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, 0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_scr[...] + jnp.log(l)


def _flash_fwd(q, k, v, *, causal: bool, window: int, bq: int, bk: int,
               interpret: bool):
    B, KV, G, S, D = q.shape
    Sk = k.shape[2]
    nq, nk = S // bq, Sk // bk
    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        scale=D ** -0.5)
    q_spec = pl.BlockSpec((1, 1, 1, bq, D),
                          lambda b, h, g, qi, ki: (b, h, g, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, g, qi, ki: (b, h, ki, 0))
    row_spec = pl.BlockSpec((1, 1, 1, 1, bq),
                            lambda b, h, g, qi, ki: (b, h, g, 0, qi))
    return pl.pallas_call(
        kernel,
        grid=(B, KV, G, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, KV, G, 1, S), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bq), F32),
            pltpu.VMEM((1, bq), F32),
            pltpu.VMEM((D, bq), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward (flash-2: recompute p from lse; dkv sweep then dq sweep)
# ---------------------------------------------------------------------------

def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
               bq, bk, causal, window, scale):
    """Shared recompute: (q, k, do, pT, dsT) for one (q block, kv block)."""
    q = q_ref[0, 0, 0]                                 # (bq, D)
    k = k_ref[0, 0]                                    # (bk, D)
    v = v_ref[0, 0]
    do = do_ref[0, 0, 0]                               # (bq, D)
    s = _dot(k, q, _NT) * scale                        # (bk, bq)
    mask = _mask_t(qi, ki, bq, bk, causal, window)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0, 0]), 0.0)
    dp = _dot(v, do, _NT)                              # (bk, bq)
    ds = p * (dp - delta_ref[0, 0, 0]) * scale
    return q, k, do, p, ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, bq: int, bk: int, nq: int, ng: int, causal: bool,
                    window: int, scale: float):
    # grid (B, KV, nk, G, nq): the (g, qi) sweep is sequential so dk/dv for a
    # kv block accumulate over every query group and q block in scratch
    ki, gi, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q, _, do, p, ds = _bwd_block(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, bq=bq,
        bk=bk, causal=causal, window=window, scale=scale)
    dv_scr[...] += _dot(p, do.astype(F32), (((1,), (0,)), ((), ())))
    dk_scr[...] += _dot(ds, q.astype(F32), (((1,), (0,)), ((), ())))

    @pl.when(jnp.logical_and(gi == ng - 1, qi == nq - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, bq: int, bk: int, nk: int, causal: bool,
                   window: int, scale: float):
    qi, ki = pl.program_id(3), pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    _, k, _, _, ds = _bwd_block(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, bq=bq,
        bk=bk, causal=causal, window=window, scale=scale)
    dq_scr[...] += _dot(ds, k.astype(F32), _TN)       # (bq, D)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd(res, do, *, causal, window, bq, bk, interpret):
    q, k, v, o, lse = res
    B, KV, G, S, D = q.shape
    Sk = k.shape[2]
    nq, nk = S // bq, Sk // bk
    scale = D ** -0.5
    delta = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1)[:, :, :, None]

    # dkv grid (b, h, ki, g, qi)
    q_spec = pl.BlockSpec((1, 1, 1, bq, D),
                          lambda b, h, ki, g, qi: (b, h, g, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, g, qi: (b, h, ki, 0))
    row_spec = pl.BlockSpec((1, 1, 1, 1, bq),
                            lambda b, h, ki, g, qi: (b, h, g, 0, qi))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq, ng=G,
                          causal=causal, window=window, scale=scale),
        grid=(B, KV, nk, G, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), F32), pltpu.VMEM((bk, D), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dq grid (b, h, g, qi, ki)
    q_spec = pl.BlockSpec((1, 1, 1, bq, D),
                          lambda b, h, g, qi, ki: (b, h, g, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, g, qi, ki: (b, h, ki, 0))
    row_spec = pl.BlockSpec((1, 1, 1, 1, bq),
                            lambda b, h, g, qi, ki: (b, h, g, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          causal=causal, window=window, scale=scale),
        grid=(B, KV, G, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp over the head-major layout; public wrapper is sequence-major
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_hm(q, k, v, causal, window, bq, bk, interpret):
    o, _ = _flash_fwd(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                      interpret=interpret)
    return o


def _vjp_fwd(q, k, v, causal, window, bq, bk, interpret):
    o, lse = _flash_fwd(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                        interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, window, bq, bk, interpret, res, do):
    return _flash_bwd(res, do, causal=causal, window=window, bq=bq, bk=bk,
                      interpret=interpret)


_flash_hm.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q: (B,S,KV,G,D); k,v: (B,Sk,KV,D) -> o: (B,S,KV,G,D)."""
    qh = jnp.transpose(q, (0, 2, 3, 1, 4))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    o = _flash_hm(qh, kh, vh, causal, window, bq, bk, interpret)
    return jnp.transpose(o, (0, 3, 1, 2, 4))
