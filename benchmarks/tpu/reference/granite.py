"""Plain float32 reference of a dense GQA decoder (granite-8b's layers).

Straightforward ``jax.numpy`` at full matmul precision, with no kernel,
cache or batching trick, written from the architecture's description:
pre-norm RMSNorm blocks, causal grouped-query attention with rotary
embeddings on interleaved pairs (theta from the configuration), a SwiGLU
MLP, a final RMSNorm and an untied LM head.  It imports nothing of the
program and reads the benchmark's weight dict (``weights.py``) as it is,
upcasting one layer at a time so that it fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
              "w_down")
QUERY_BLOCK = 512


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, S, N, hd); pairs (2i, 2i+1) rotate by pos * theta^(-i/half)."""
    B, S, N, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(B, S, N, hd)


def attention(q, k, v, block: int = QUERY_BLOCK):
    """Causal softmax attention, q, k, v: (B, S, H, hd).  Queries go in
    blocks (recomputed in a backward pass) so that the scores of a long
    sequence never live whole; the arithmetic is that of one block."""
    B, S, H, hd = q.shape
    block = block if S % block == 0 else S
    scale = jnp.sqrt(jnp.asarray(hd, F32))

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) / scale
        causal = (i * block + jnp.arange(block))[:, None] >= jnp.arange(S)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(S // block))       # (n, B, blk, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def layer(x, lw, c):
    B, S, d = x.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = rmsnorm(x, lw["ln1"], eps)
    q = rope(_mm(h, lw["wq"]).reshape(B, S, H, hd), theta)
    k = rope(_mm(h, lw["wk"]).reshape(B, S, KV, hd), theta)
    v = _mm(h, lw["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)          # query head i reads KV i//G
    v = jnp.repeat(v, H // KV, axis=2)
    x = x + _mm(attention(q, k, v).reshape(B, S, H * hd), lw["wo"])
    h = rmsnorm(x, lw["ln2"], eps)
    return x + _mm(jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"]),
                   lw["w_down"])


def hidden(w, tokens, c):
    """Final hidden states (B, S, d) in f32."""
    x = w["embed"].astype(F32)[tokens]

    def body(x, lw):
        return layer(x, {k: v.astype(F32) for k, v in lw.items()}, c), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    return rmsnorm(x, w["final_norm"].astype(F32), c["rms_norm_eps"])


def logits(w, tokens, c):
    return _mm(hidden(w, tokens, c), w["unembed"].astype(F32))


def _config_items(c):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _served_gaps(w, tokens, starts, served, cfg_items):
    lg = logits(w, tokens, dict(cfg_items))                   # (R, S, V)
    pos = starts[:, None] + jnp.arange(served.shape[1])[None, :]
    lg = jnp.take_along_axis(lg, pos[..., None], axis=1)      # (R, N, V)
    got = jnp.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    return lg.max(axis=-1) - got


def served_gaps(w, tokens, starts, served, c):
    """For each served token, how far its reference logit lies below the
    reference's best at that position.

    tokens (R, S): each request's prompt and served tokens, zero-padded;
    starts (R,): the position whose logits chose the first served token;
    served (R, N): the served tokens.  Returns (R, N) f32 gaps, 0 where the
    served token is the reference's own greedy choice."""
    return _served_gaps(w, tokens, starts, served, _config_items(c))
