"""Plain float32 reference of Mixtral's decoder (arXiv:2401.04088).

Straightforward ``jax.numpy`` at full matmul precision, with no kernel,
cache or batching trick.  Attention is granite's (``reference/granite.py``:
pre-norm RMSNorm, causal grouped-query attention, rotary embeddings on
interleaved pairs); the FFN of every layer is a sparse mixture of SwiGLU
experts: the router's logits over all experts, their softmax in f32, the
top ``num_experts_per_tok`` probabilities renormalized to sum to one, and
every expert computed on every token, weighted by its renormalized weight
(zero where it was not chosen): the plainest dropless form.  A share of
the experts (``expert_share`` (first, count), held in the weights) adds
only its own experts' part, so the shares' results add up to the whole
layer.  It imports nothing of the program and reads the benchmark's
weight dict (``weights_moe.py``) as it is; it upcasts one layer's
attention and one expert at a time so that it fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import granite

F32 = jnp.float32
_mm = granite._mm
ATTN_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2")


def attention(x, lw, c):
    """x + the attention block's output; ``lw`` holds f32 weights."""
    B, S, d = x.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = granite.rmsnorm(x, lw["ln1"], eps)
    q = granite.rope(_mm(h, lw["wq"]).reshape(B, S, H, hd), theta)
    k = granite.rope(_mm(h, lw["wk"]).reshape(B, S, KV, hd), theta)
    v = _mm(h, lw["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)          # query head i reads KV i//G
    v = jnp.repeat(v, H // KV, axis=2)
    return x + _mm(granite.attention(q, k, v).reshape(B, S, H * hd),
                   lw["wo"])


def routing(h, router, k):
    """(router logits (..., E), dense weights (..., E): each token's top-k
    softmax probabilities renormalized, zero elsewhere)."""
    logits = _mm(h, router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    E = logits.shape[-1]
    return logits, jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32)
                           * top_w[..., None], axis=-2)


def experts(h, weight, w, l, first: int = 0):
    """Layer ``l``'s held experts' part of the FFN: the sum over held e of
    weight[..., first + e] * SwiGLU_e(h).  ``w`` holds the stacked
    (L, count, ...) expert weights as stored; one expert at a time is
    sliced out and upcast."""
    def one(acc, e):
        g = _mm(h, w["w_gate"][l, e].astype(F32))
        u = _mm(h, w["w_up"][l, e].astype(F32))
        y = _mm(jax.nn.silu(g) * u, w["w_down"][l, e].astype(F32))
        return acc + jnp.take(weight, first + e, axis=-1)[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(w["w_gate"].shape[1]))
    return out


def ffn(h, w, l, c, first: int = 0):
    """(layer ``l``'s held experts' part of the MoE FFN on h, its router
    logits)."""
    logits, weight = routing(h, w["router"][l].astype(F32),
                             c["num_experts_per_tok"])
    return experts(h, weight, w, l, first), logits


def hidden(w, tokens, c, first: int = 0):
    """Final hidden states (B, S, d) in f32, and for each position the
    smallest margin over the layers between the router's k-th and
    (k+1)-th logits (how near the top-k choice came to another expert;
    +inf with every expert chosen)."""
    L, k = c["num_hidden_layers"], c["num_experts_per_tok"]
    x = w["embed"].astype(F32)[tokens]

    def layer(l, carry):
        x, margin = carry
        lw = {n: w[n][l].astype(F32) for n in ATTN_KEYS}
        x = attention(x, lw, c)
        h = granite.rmsnorm(x, lw["ln2"], c["rms_norm_eps"])
        out, logits = ffn(h, w, l, c, first)
        if logits.shape[-1] > k:
            top = jax.lax.top_k(logits, k + 1)[0]
            margin = jnp.minimum(margin, top[..., k - 1] - top[..., k])
        return x + out, margin

    margin = jnp.full(tokens.shape, jnp.inf, F32)
    x, margin = jax.lax.fori_loop(0, L, layer, (x, margin))
    return granite.rmsnorm(x, w["final_norm"].astype(F32),
                           c["rms_norm_eps"]), margin


@functools.partial(jax.jit, static_argnames=("cfg_items", "first"))
def _served_gaps(w, tokens, starts, served, cfg_items, first):
    c = dict(cfg_items)
    x, margin = hidden(w, tokens, c, first)
    pos = starts[:, None] + jnp.arange(served.shape[1])[None, :]
    x = jnp.take_along_axis(x, pos[..., None], axis=1)        # (R, N, d)
    lg = _mm(x, w["unembed"].astype(F32))                     # (R, N, V)
    got = jnp.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    return lg.max(axis=-1) - got, jnp.take_along_axis(margin, pos, axis=1)


def served_gaps(w, tokens, starts, served, c, first: int = 0):
    """For each served token, how far its reference logit lies below the
    reference's best at that position, and the router's smallest top-k
    margin there (``hidden``).

    tokens (R, S): each request's prompt and served tokens, zero-padded;
    starts (R,): the position whose logits chose the first served token;
    served (R, N): the served tokens.  Returns two (R, N) f32 arrays."""
    return _served_gaps(w, tokens, starts, served, granite._config_items(c),
                        first)
