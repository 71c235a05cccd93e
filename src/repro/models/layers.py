"""Shared transformer layers: norms, RoPE, blockwise GQA attention, MLPs.

Design points (see DESIGN.md §6):
* pure functions over param pytrees; params are created by ``init`` fns and
  described by matching *logical sharding* trees (distributed/sharding.py);
* attention is blockwise (flash-style online softmax in pure JAX): memory per
  step is O(Bq x Bk), required for the 32k/500k shapes;
* RoPE uses the interleaved (GPT-J) pairing so head_dim stays shardable;
* GQA is computed in grouped form (B, S, KV, G, D) — no materialized repeat;
* sliding-window attention slices a static-width band per q block, so SWA
  FLOPs scale with S*W, not S^2 (what makes long_500k viable for mixtral
  and hymba).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def init_rmsnorm(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype=dtype)


# ---------------------------------------------------------------------------
# RoPE (interleaved pairing)
# ---------------------------------------------------------------------------

def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, ..., D) with pairs (2i, 2i+1); pos: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None] * freqs              # (B, S, half)
    # broadcast over intermediate dims (heads etc.)
    extra = x.ndim - 3
    ang = ang.reshape(ang.shape[0], ang.shape[1], *([1] * extra), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32).reshape(*x.shape[:-1], half, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    r0 = x0 * cos - x1 * sin
    r1 = x0 * sin + x1 * cos
    return jnp.stack([r0, r1], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention parameters
# ---------------------------------------------------------------------------

class AttnParams(NamedTuple):
    wq: jax.Array            # (d, H*hd)
    wk: jax.Array            # (d, KV*hd)
    wv: jax.Array            # (d, KV*hd)
    wo: jax.Array            # (H*hd, d)
    bq: Optional[jax.Array]  # (H*hd,) or None
    bk: Optional[jax.Array]
    bv: Optional[jax.Array]


def init_attn(key, cfg: ModelConfig, dtype) -> AttnParams:
    d, hd, H, KV = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return AttnParams(
        wq=(jax.random.normal(k1, (d, H * hd)) * s).astype(dtype),
        wk=(jax.random.normal(k2, (d, KV * hd)) * s).astype(dtype),
        wv=(jax.random.normal(k3, (d, KV * hd)) * s).astype(dtype),
        wo=(jax.random.normal(k4, (H * hd, d)) * (H * hd) ** -0.5).astype(dtype),
        bq=jnp.zeros((H * hd,), dtype) if cfg.qkv_bias else None,
        bk=jnp.zeros((KV * hd,), dtype) if cfg.qkv_bias else None,
        bv=jnp.zeros((KV * hd,), dtype) if cfg.qkv_bias else None,
    )


def attn_specs(cfg: ModelConfig) -> AttnParams:
    b = ("heads",) if cfg.qkv_bias else None
    return AttnParams(
        wq=("fsdp", "heads"), wk=("fsdp", "heads"), wv=("fsdp", "heads"),
        wo=("heads", "fsdp"),
        bq=b, bk=b, bv=b,
    )


# ---------------------------------------------------------------------------
# Blockwise attention (training / prefill)
# ---------------------------------------------------------------------------

def _qkv(x: jax.Array, p: AttnParams, cfg: ModelConfig, pos: jax.Array):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p.wq
    k = x @ p.wk
    v = x @ p.wv
    if p.bq is not None:
        q, k, v = q + p.bq, k + p.bk, v + p.bv
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def _grouped(q: jax.Array, KV: int):
    """(B, S, H, D) -> (B, S, KV, G, D)."""
    B, S, H, D = q.shape
    return q.reshape(B, S, KV, H // KV, D)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool, window: int,
                        q_block: int, kv_block: int) -> jax.Array:
    """Flash-style attention.  q: (B,S,H,D); k,v: (B,S,KV,D) -> (B,S,H,D).

    Full-causal mode scans all kv blocks per q block with masking (the upper
    triangle is computed-and-masked: a known 2x FLOP envelope, recorded in
    the roofline notes).  Sliding-window mode slices a static (window +
    q_block)-wide band per q block, giving S*W scaling.
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    scale = D ** -0.5
    nq = S // q_block
    assert S % q_block == 0 and Sk % kv_block == 0, (S, Sk, q_block, kv_block)
    qg = _grouped(q, KV)                                   # (B,S,KV,G,D)

    def one_q_block(qi):
        qs = jax.lax.dynamic_slice_in_dim(qg, qi * q_block, q_block, axis=1)
        q_pos = qi * q_block + jnp.arange(q_block)
        if window > 0:
            band = min(window + q_block, Sk)
            nkb = -(-band // kv_block)
            k_start = jnp.maximum(qi * q_block + q_block - band, 0)
            k_start = jnp.minimum(k_start, Sk - nkb * kv_block)
            k_start = jnp.maximum(k_start, 0)
        else:
            nkb = Sk // kv_block
            k_start = 0

        def kv_step(carry, kb_idx):
            m, l, acc = carry
            start = k_start + kb_idx * kv_block
            ks = jax.lax.dynamic_slice_in_dim(k, start, kv_block, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, kv_block, axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qs.astype(F32),
                           ks.astype(F32)) * scale       # (B,KV,G,Bq,Bk)
            k_pos = start + jnp.arange(kv_block)
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vs.astype(F32))
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, KV, G, q_block), NEG_INF, F32),
            jnp.zeros((B, KV, G, q_block), F32),
            jnp.zeros((B, KV, G, q_block, D), F32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nkb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,KV,G,Bq,D)
        return jnp.transpose(out, (0, 3, 1, 2, 4))         # (B,Bq,KV,G,D)

    # checkpoint each q block: backward recomputes the kv scan instead of
    # storing per-kv-step residuals (flash-attention backward memory shape).
    # The named scope names this region in the compiled HLO and in device
    # traces (on TPU the Pallas flash kernel takes its place).
    with jax.named_scope("flash_attn_interior"):
        outs = jax.lax.map(jax.checkpoint(one_q_block),
                           jnp.arange(nq))                 # (nq,B,Bq,KV,G,D)
    out = jnp.transpose(outs, (1, 0, 2, 3, 4, 5)).reshape(B, S, H, D)
    return out.astype(q.dtype)


def _flash_mode() -> Optional[bool]:
    """None = off; True = real TPU kernel; False = interpret (tests)."""
    import os
    if os.environ.get("REPRO_FORCE_FLASH") == "1":
        return jax.default_backend() == "tpu"
    return True if jax.default_backend() == "tpu" else None


def _flash_per_shard(q, k, v, **kw):
    """The Pallas flash kernel, run on each device's shard under a mesh.

    q: (B, S, H, D); k, v: (B, S, KV, D) -> (B, S, H, D).  GSPMD cannot
    partition a Mosaic kernel, so under a mesh the call goes through
    ``shard_map``: batch split over the data axes, query heads over 'model'
    where they divide, sequence whole.  Where the KV heads divide 'model'
    too, each shard takes its own KV heads.  Where they do not (e.g. 8 KV
    heads on a model axis of 16) but each shard's query heads share one KV
    head, k/v stay whole and each shard slices that head.  Otherwise every
    shard computes every head.
    """
    from repro.kernels.flash_attention import flash_attention
    KV = k.shape[2]

    def call(q, k, v):
        o = flash_attention(_grouped(q, k.shape[2]), k, v, **kw)
        return o.reshape(q.shape)

    r = shd.get_rules()
    if r is None or r.mesh is None:
        return call(q, k, v)
    b = r.resolve("batch", q.shape[0])
    h = r.resolve("heads", q.shape[2])
    G = q.shape[2] // KV
    local = q.shape[2] // r.axis_size(h) if h else 0
    q_spec = P(b, None, h, None)
    if h is None or r.resolve("heads", KV) == h:
        kv_spec = P(b, None, h, None)
        body = call
    elif G % local == 0:
        kv_spec = P(b, None, None, None)

        def body(q, k, v):
            head = jax.lax.axis_index(h) * local // G
            one = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=head,
                                    slice_size=1, axis=2)
            return call(q, one(k), one(v))
    else:
        q_spec = kv_spec = P(b, None, None, None)
        body = call
    return jax.shard_map(body, mesh=r.mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def attention(x: jax.Array, p: AttnParams, cfg: ModelConfig, pos: jax.Array,
              q_block: int, kv_block: int,
              window_override: Optional[int] = None,
              causal: bool = True) -> jax.Array:
    """Full training/prefill self-attention with output projection.

    On TPU the inner loops run as the Pallas flash kernel (VMEM-resident
    s/p blocks); elsewhere the pure-jnp blockwise path is used (same math,
    validated equal in tests/test_flash_attention.py).
    """
    B, S, _ = x.shape
    q, k, v = _qkv(x, p, cfg, pos)
    # inside attention: gather seq, shard heads (TP); the residual
    # stream between layers stays seq-sharded
    q = shd.act(q, "batch", None, "heads", None)
    # k/v: gather the seq dim BEFORE the block loops — dynamic-slicing a
    # seq-sharded tensor forces involuntary full remat in SPMD
    k = shd.act(k, "batch", None, None, None)
    v = shd.act(v, "batch", None, None, None)
    k = checkpoint_name(k, "kv_gathered")
    v = checkpoint_name(v, "kv_gathered")
    window = cfg.sliding_window if window_override is None else window_override
    if window >= S:
        window = 0  # band covers everything: plain causal
    qb = min(q_block, S)
    kb = min(kv_block, S)
    if S % qb:
        qb = S   # odd lengths (e.g. vlm prefix + text): single block
    if S % kb:
        kb = S
    flash = _flash_mode()
    if flash is not None and S % qb == 0 and k.shape[1] % kb == 0:
        o = _flash_per_shard(q, k, v, causal=causal, window=window, bq=qb,
                             bk=kb, interpret=not flash)
    else:
        o = blockwise_attention(q, k, v, causal=causal, window=window,
                                q_block=qb, kv_block=kb)
    o = shd.act(o, "batch", None, "heads", None)
    out = o.reshape(B, S, -1) @ p.wo
    return checkpoint_name(shd.act(out, "batch", "seq", None), "proj_out")


def cross_attention(x: jax.Array, memory: jax.Array, p: AttnParams,
                    cfg: ModelConfig, q_block: int, kv_block: int) -> jax.Array:
    """Encoder-decoder cross attention (no RoPE on memory side)."""
    B, S, _ = x.shape
    M = memory.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p.wq).reshape(B, S, H, hd)
    k = (memory @ p.wk).reshape(B, M, KV, hd)
    v = (memory @ p.wv).reshape(B, M, KV, hd)
    qb, kb = min(q_block, S), min(kv_block, M)
    if S % qb or M % kb:
        qb, kb = S, M  # tiny shapes: single block
    o = blockwise_attention(q, k, v, causal=False, window=0,
                            q_block=qb, kv_block=kb)
    return o.reshape(B, S, -1) @ p.wo


# ---------------------------------------------------------------------------
# Decode-step attention with KV cache
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array        # (B, S_cache, KV, D)
    v: jax.Array
    # scales are present only for packed (int8/int4) caches
    k_scale: Optional[jax.Array]  # (B, S_cache, KV, 1) f32
    v_scale: Optional[jax.Array]


def cache_specs(bits: int = 16) -> KVCache:
    s = ("batch", "cache_seq", None, None) if bits != 16 else None
    return KVCache(
        k=("batch", "cache_seq", None, None),
        v=("batch", "cache_seq", None, None),
        k_scale=s,
        v_scale=s,
    )


def init_cache(cfg: ModelConfig, batch: int, s_cache: int, bits: int,
               dtype=jnp.bfloat16) -> KVCache:
    KV, hd = cfg.n_kv_heads, cfg.hd
    if bits == 16:
        z = jnp.zeros((batch, s_cache, KV, hd), dtype)
        return KVCache(z, z, None, None)
    cd = hd if bits == 8 else hd // 2
    z = jnp.zeros((batch, s_cache, KV, cd), jnp.int8)
    s = jnp.ones((batch, s_cache, KV, 1), F32)
    return KVCache(z, z, s, s)


def _quant_rows(x: jax.Array, bits: int):
    """Symmetric per-(pos, head) quantization of (..., D) to int8/int4."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -qmax, qmax).astype(jnp.int32)
    if bits == 8:
        return q.astype(jnp.int8), scale
    pairs = (q & 0xF).reshape(*q.shape[:-1], -1, 2)  # contiguous, no gather
    return (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.int8), scale


def _dequant_rows(codes: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    c = codes.astype(jnp.int32)
    if bits == 8:
        q = c
    else:
        def sext4(x):
            return ((x & 0xF) ^ 0x8) - 0x8
        q = jnp.stack([sext4(c), sext4(c >> 4)], axis=-1).reshape(
            *c.shape[:-1], -1)
    return q.astype(F32) * scale


def update_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, bits: int) -> KVCache:
    """Insert (B, 1, KV, D) new kv at per-batch position ``pos`` (B,).

    One indexed scatter per cache array writes every sequence's row: a
    ``dynamic_update_slice`` per sequence compiles on the TPU to a serial
    loop over the sequences.  ``pos`` is clamped into the cache, as
    ``dynamic_update_slice`` clamps its start, so the indices are in bounds.
    """
    B, S = cache.k.shape[:2]
    rows, slot = jnp.arange(B), jnp.clip(pos, 0, S - 1)

    def put(x, new):
        return x.at[rows, slot].set(new[:, 0].astype(x.dtype),
                                    mode="promise_in_bounds")

    if bits == 16:
        return KVCache(put(cache.k, k_new), put(cache.v, v_new), None, None)
    kq, ks = _quant_rows(k_new, bits)
    vq, vs = _quant_rows(v_new, bits)
    return KVCache(put(cache.k, kq), put(cache.v, vq),
                   put(cache.k_scale, ks), put(cache.v_scale, vs))


def decode_attention(x: jax.Array, p: AttnParams, cfg: ModelConfig,
                     cache: KVCache, pos: jax.Array, bits: int,
                     window: int = 0) -> Tuple[jax.Array, KVCache]:
    """One-token attention against the cache.  x: (B, 1, d); pos: (B,).

    When the cache is shorter than the sequence (sliding-window models) it is
    treated as a ring buffer: slot j holds the key written at global position
    ``pos - ((pos - j) mod S_cache)`` — the rolling window that makes
    long_500k decoding O(window) instead of O(S).
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    S = cache.k.shape[1]
    ring = window > 0 and S <= window
    slot = pos % S if ring else pos
    q, k_new, v_new = _qkv(x, p, cfg, pos[:, None])
    # the new row's quantize and its write into the cache, scoped for the
    # benchmark's device trace (kv_write_ms.serve)
    with jax.named_scope("kv_cache_write"):
        cache = update_cache(cache, k_new, v_new, slot, bits)

    # the dequant + attention region, scoped for the benchmark's device trace
    # (kv_attn_ms.serve).  It runs in XLA: on the TPU the dequant of the whole
    # cache is a multiply-convert fusion per step, not a fused kernel.
    # k/v stay in bf16 with f32 MXU accumulation: a whole-cache .astype(F32)
    # gets hoisted out of the layer loop by XLA, doubling cache residency.
    with jax.named_scope("decode_attn_interior"):
        cdt = x.dtype
        if bits == 16:
            k, v = cache.k, cache.v
        else:
            k = _dequant_rows(cache.k, cache.k_scale, bits).astype(cdt)
            v = _dequant_rows(cache.v, cache.v_scale, bits).astype(cdt)
        k = shd.act(k, "batch", "cache_seq", None, None)
        v = shd.act(v, "batch", "cache_seq", None, None)

        j = jnp.arange(S)[None, :]                        # (1, S)
        if ring:
            k_pos = pos[:, None] - ((pos[:, None] - j) % S)
            valid = k_pos >= 0
        else:
            k_pos = j
            valid = k_pos <= pos[:, None]
            if window > 0:
                valid &= (pos[:, None] - k_pos) < window
        qg = _grouped(q, KV).astype(k.dtype)              # (B,1,KV,G,D)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                       preferred_element_type=F32) * (hd ** -0.5)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        p_attn = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p_attn.astype(k.dtype), v,
                       preferred_element_type=F32)        # (B,KV,G,1,D)
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(B, 1, H * hd)
    return (o.astype(x.dtype) @ p.wo), cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

class MlpParams(NamedTuple):
    w_gate: Optional[jax.Array]  # (d, ff) — None for gelu
    w_up: jax.Array              # (d, ff)
    w_down: jax.Array            # (ff, d)


def init_mlp(key, d: int, ff: int, act: str, dtype) -> MlpParams:
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    return MlpParams(
        w_gate=(jax.random.normal(k1, (d, ff)) * s).astype(dtype)
        if act == "swiglu" else None,
        w_up=(jax.random.normal(k2, (d, ff)) * s).astype(dtype),
        w_down=(jax.random.normal(k3, (ff, d)) * ff ** -0.5).astype(dtype),
    )


def mlp_specs(act: str) -> MlpParams:
    return MlpParams(
        w_gate=("fsdp", "ff") if act == "swiglu" else None,
        w_up=("fsdp", "ff"),
        w_down=("ff", "fsdp"),
    )


def mlp(x: jax.Array, p: MlpParams, act: str) -> jax.Array:
    if act == "swiglu":
        h = jax.nn.silu(x @ p.w_gate) * (x @ p.w_up)
    else:
        h = jax.nn.gelu(x @ p.w_up)
    h = shd.act(h, "batch", None, "ff")
    return checkpoint_name(h @ p.w_down, "proj_out")


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------

class EmbedParams(NamedTuple):
    table: jax.Array        # (V, d)
    unembed: Optional[jax.Array]  # (d, V) — None when tied
    final_norm: jax.Array


def init_embed(key, cfg: ModelConfig, dtype) -> EmbedParams:
    k1, k2 = jax.random.split(key)
    return EmbedParams(
        table=(jax.random.normal(k1, (cfg.vocab, cfg.d_model)) * 0.02).astype(dtype),
        unembed=None if cfg.tie_embeddings else
        (jax.random.normal(k2, (cfg.d_model, cfg.vocab))
         * cfg.d_model ** -0.5).astype(dtype),
        final_norm=init_rmsnorm(cfg.d_model, dtype),
    )


def embed_specs(cfg: ModelConfig) -> EmbedParams:
    return EmbedParams(
        table=("vocab", "fsdp"),
        unembed=None if cfg.tie_embeddings else ("fsdp", "vocab"),
        final_norm=(None,),
    )


def embed(tokens: jax.Array, p: EmbedParams) -> jax.Array:
    return jnp.take(p.table, tokens, axis=0)


def logits(x: jax.Array, p: EmbedParams, cfg: ModelConfig) -> jax.Array:
    x = rmsnorm(x, p.final_norm, cfg.norm_eps)
    w = p.table.T if cfg.tie_embeddings else p.unembed
    out = x @ w
    # logits are the largest activation: shard S over 'model' when sequence
    # sharding is active (keeps (B, S/tp, V)); otherwise shard the vocab dim
    r = shd.get_rules()
    if r is not None and out.ndim == 3 and \
            r.resolve("seq", out.shape[1]) is not None:
        return shd.act(out, "batch", "seq", None)
    return shd.act(out, "batch", None, "vocab")


def cross_entropy(lg: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; stable in f32."""
    lg = lg.astype(F32)
    m = lg.max(axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def fused_ce_loss(x: jax.Array, p: EmbedParams, cfg: ModelConfig,
                  labels: jax.Array, mask: Optional[jax.Array] = None,
                  chunk: int = 512) -> jax.Array:
    """Unembed + cross-entropy fused over sequence chunks.

    Never materializes the (B, S, V) logits tensor (at 150k vocab that is the
    peak-memory hog of the whole train step): each chunk computes (B, C, V)
    logits with V sharded over 'model', reduces to per-token NLL, and is
    checkpointed so backward recomputes the chunk instead of keeping it.
    """
    B, S, _ = x.shape
    x = rmsnorm(x, p.final_norm, cfg.norm_eps)
    x = shd.act(x, "batch", None, None)             # gather seq for chunking
    w = p.table.T if cfg.tie_embeddings else p.unembed
    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)

    @jax.checkpoint
    def one_chunk(xc, lc, mc):
        lg = (xc @ w).astype(F32)
        lg = shd.act(lg, "batch", None, "vocab")
        m = lg.max(axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return jnp.sum(nll), jnp.sum(mc)

    total, count = jnp.zeros((), F32), jnp.zeros((), F32)
    for ci in range(n_chunks):
        lo = ci * chunk
        hi = min(lo + chunk, S)
        mc = (mask[:, lo:hi].astype(F32) if mask is not None
              else jnp.ones((B, hi - lo), F32))
        t, c = one_chunk(x[:, lo:hi], labels[:, lo:hi], mc)
        total, count = total + t, count + c
    return total / jnp.maximum(count, 1.0)
