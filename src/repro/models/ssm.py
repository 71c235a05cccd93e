"""Mamba-2 SSD mixer (state-space duality, arXiv:2405.21060).

Chunked SSD: within a chunk the recurrence is computed in its dual quadratic
(attention-like) form on the MXU; across chunks a compact state
(H, N, P) is carried — which is itself a MARS-shaped flow (atomic,
irredundant inter-chunk block), see DESIGN.md §5.

Shapes: d_inner = expand * d_model, P = ssm_head, H = d_inner / P,
N = ssm_state.  B/C are shared across heads (n_groups = 1, as in the 130m
model).  A is per-head scalar decay; dt per head via softplus.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd

F32 = jnp.float32


class SsmParams(NamedTuple):
    in_proj: jax.Array       # (d, 2*di + 2*N + H)
    conv_w: jax.Array        # (K, di + 2*N) depthwise causal conv
    conv_b: jax.Array        # (di + 2*N,)
    a_log: jax.Array         # (H,)
    dt_bias: jax.Array       # (H,)
    d_skip: jax.Array        # (H,)
    gate_norm: jax.Array     # (di,)
    out_proj: jax.Array      # (di, d)


def init_ssm(key, cfg: ModelConfig, dtype) -> SsmParams:
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    K = cfg.ssm_conv
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    return SsmParams(
        in_proj=(jax.random.normal(k1, (d, 2 * di + 2 * N + H)) * s).astype(dtype),
        conv_w=(jax.random.normal(k2, (K, di + 2 * N)) * K ** -0.5).astype(dtype),
        conv_b=jnp.zeros((di + 2 * N,), dtype),
        a_log=jnp.log(jnp.linspace(1.0, 16.0, H)).astype(F32),
        dt_bias=jnp.full((H,), -4.6, F32),   # softplus^-1(0.01)
        d_skip=jnp.ones((H,), F32),
        gate_norm=jnp.ones((di,), dtype),
        out_proj=(jax.random.normal(k3, (di, d)) * di ** -0.5).astype(dtype),
    )


def ssm_specs() -> SsmParams:
    return SsmParams(
        in_proj=("fsdp", "tp"), conv_w=(None, "tp"), conv_b=("tp",),
        a_log=(None,), dt_bias=(None,), d_skip=(None,),
        gate_norm=("tp",), out_proj=("tp", "fsdp"),
    )


def _split(zxbcdt: jax.Array, cfg: ModelConfig):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xin = zxbcdt[..., di:2 * di]
    b = zxbcdt[..., 2 * di:2 * di + N]
    c = zxbcdt[..., 2 * di + N:2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N:]
    return z, xin, b, c, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv along S.  x: (B, S, C); w: (K, C).

    Returns (y, new_state) where state holds the last K-1 inputs.
    """
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else None
    return jax.nn.silu(y + b), new_state


def ssd_forward(params: SsmParams, x: jax.Array, cfg: ModelConfig
                ) -> jax.Array:
    """Training/prefill SSD.  x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    di, N, H, P, Q = (cfg.d_inner, cfg.ssm_state, cfg.ssm_heads,
                      cfg.ssm_head, cfg.ssm_chunk)
    Q = min(Q, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    z, xin, b, c, dt_raw = _split(x @ params.in_proj, cfg)
    xbc, _ = _causal_conv(jnp.concatenate([xin, b, c], axis=-1),
                          params.conv_w, params.conv_b)
    xin, b, c = (xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:])

    dt = jax.nn.softplus(dt_raw.astype(F32) + params.dt_bias)     # (B,S,H)
    a = -jnp.exp(params.a_log)                                    # (H,)
    da = dt * a                                                   # (B,S,H) <0
    # §Perf: the (B,S,H,P)-shaped tensors stream through HBM per layer pass;
    # keep them in the activation dtype and upcast chunk-locally only —
    # measured 9.6 GB/layer-pass of f32 xdt/y traffic otherwise (mamba2
    # train_4k iteration log, EXPERIMENTS.md)
    adt = x.dtype
    xh = xin.reshape(B, S, H, P)
    xdt = xh * dt[..., None].astype(adt)

    # chunk views, scanned one chunk at a time (keeps the dual-form Q x Q
    # tensors chunk-local — the inter-chunk state is the only carried block)
    da_c = jnp.moveaxis(da.reshape(B, nc, Q, H), 1, 0)            # (nc,B,Q,H)
    b_c = jnp.moveaxis(b.reshape(B, nc, Q, N), 1, 0)
    c_c = jnp.moveaxis(c.reshape(B, nc, Q, N), 1, 0)
    xdt_c = jnp.moveaxis(xdt.reshape(B, nc, Q, H, P), 1, 0)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk_step(h, inputs):
        da_n, b_n, c_n, xdt_n = inputs
        b_n, c_n = b_n.astype(F32), c_n.astype(F32)   # chunk-local upcast
        xdt_n = xdt_n.astype(F32)
        cs = jnp.cumsum(da_n, axis=1)                             # (B,Q,H)
        cb = jnp.einsum("bim,bjm->bij", c_n, b_n)                 # (B,Q,Q)
        decay = jnp.exp(cs[:, :, None, :] - cs[:, None, :, :])    # (B,Q,Q,H)
        att = jnp.where(tri[None, :, :, None], cb[..., None] * decay, 0.0)
        y_intra = jnp.einsum("bijh,bjhp->bihp", att, xdt_n)
        y_inter = jnp.einsum("bim,bhmp,bih->bihp", c_n, h, jnp.exp(cs))
        seg = jnp.exp(cs[:, -1:, :] - cs)                         # (B,Q,H)
        s_chunk = jnp.einsum("bjm,bjh,bjhp->bhmp", b_n, seg, xdt_n)
        h_new = jnp.exp(cs[:, -1, :])[:, :, None, None] * h + s_chunk
        return h_new, (y_intra + y_inter).astype(adt)

    init = jnp.zeros((B, H, N, P), F32)
    # scoped so that the compiled HLO and device traces name the chunk scan
    with jax.named_scope("ssd_interior"):
        _, y_c = jax.lax.scan(chunk_step, init, (da_c, b_c, c_c, xdt_c))
    y = jnp.moveaxis(y_c, 0, 1).reshape(B, S, H, P)               # (B,S,H,P)
    y = y + params.d_skip.astype(adt)[None, None, :, None] * xh
    y = y.reshape(B, S, di).astype(x.dtype)

    # gate + norm + out
    y = y * jax.nn.silu(z)
    from .layers import rmsnorm
    y = rmsnorm(y, params.gate_norm, cfg.norm_eps)
    return y @ params.out_proj


# ---------------------------------------------------------------------------
# Decode (recurrent form, O(1) per token)
# ---------------------------------------------------------------------------

class SsmState(NamedTuple):
    h: jax.Array           # (B, H, N, P) f32
    conv: jax.Array        # (B, K-1, di + 2N)


def init_ssm_state(cfg: ModelConfig, batch: int) -> SsmState:
    return SsmState(
        h=jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head), F32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1,
                        cfg.d_inner + 2 * cfg.ssm_state), F32),
    )


def ssm_state_specs() -> SsmState:
    return SsmState(h=("batch", None, None, None), conv=("batch", None, None))


def ssd_decode(params: SsmParams, x: jax.Array, state: SsmState,
               cfg: ModelConfig) -> Tuple[jax.Array, SsmState]:
    """x: (B, 1, d) -> (y (B, 1, d), new state)."""
    B = x.shape[0]
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head
    z, xin, b, c, dt_raw = _split(x @ params.in_proj, cfg)
    xbc = jnp.concatenate([xin, b, c], axis=-1)           # (B,1,di+2N)
    conv_in = jnp.concatenate([state.conv.astype(x.dtype), xbc], axis=1)
    y = sum(conv_in[:, i:i + 1, :] * params.conv_w[i]
            for i in range(cfg.ssm_conv))
    xbc_out = jax.nn.silu(y + params.conv_b)
    new_conv = conv_in[:, 1:, :].astype(F32)
    xin, b, c = (xbc_out[..., :di], xbc_out[..., di:di + N],
                 xbc_out[..., di + N:])

    dt = jax.nn.softplus(dt_raw[:, 0].astype(F32) + params.dt_bias)  # (B,H)
    a = -jnp.exp(params.a_log)
    da = jnp.exp(dt * a)                                  # (B,H)
    xh = xin[:, 0].reshape(B, H, P).astype(F32)
    bx = jnp.einsum("bm,bhp->bhmp", b[:, 0].astype(F32), xh * dt[..., None])
    h = da[:, :, None, None] * state.h + bx
    yh = jnp.einsum("bm,bhmp->bhp", c[:, 0].astype(F32), h)
    yh = yh + params.d_skip[None, :, None] * xh
    yflat = yh.reshape(B, 1, di).astype(x.dtype)
    yflat = yflat * jax.nn.silu(z)
    from .layers import rmsnorm
    yflat = rmsnorm(yflat, params.gate_norm, cfg.norm_eps)
    return yflat @ params.out_proj, SsmState(h=h, conv=new_conv)
