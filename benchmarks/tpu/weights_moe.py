"""Random weights of a Mixtral-style MoE decoder, made on the device from
the seed.

The layout is ``weights.py``'s plain dict (layers stacked on a leading
axis) with the dense MLP replaced by a router over all experts and the
held experts' SwiGLU weights, ``(L, count, ...)``.  Drawn as ``weights.py``
draws granite's: norm gains 1 + 0.1 N(0, 1), matmuls N(0, 1/fan_in) (the
router's fan-in is d), the embedding N(0, 0.02^2), each drawn in f32 and
cast.  Every expert has its own key, so a share of the experts holds the
same numbers as the whole layer does for them.  Each leaf is made by its
own call and the layers of a stacked leaf one at a time, so that no f32
copy of a large leaf is ever whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
from weights import _std, seed_key

GAINS = ("final_norm", "ln1", "ln2")
EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def share(c: dict):
    """(first, count) of the experts the configuration holds."""
    first, count = c.get("expert_share", (0, c["num_local_experts"]))
    return int(first), int(count)


def shapes(c: dict) -> dict:
    d, ff, H, KV, hd, L, V = counts.dims(c)
    E = c["num_local_experts"]
    _, n = share(c)
    return {
        "embed": (V, d), "unembed": (d, V), "final_norm": (d,),
        "ln1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
        "wv": (L, d, KV * hd), "wo": (L, H * hd, d), "ln2": (L, d),
        "router": (L, d, E), "w_gate": (L, n, d, ff), "w_up": (L, n, d, ff),
        "w_down": (L, n, ff, d),
    }


def _draw(key, name, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * _std(name, shape)
    if name in GAINS:
        x = x + 1.0
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("name", "shape", "dtype",
                                             "first"))
def _leaf(key, name, shape, dtype, first):
    if name in EXPERT_KEYS:           # (L, n, ...): one key per expert
        L, n = shape[:2]

        def one(le):
            k = jax.random.fold_in(jax.random.fold_in(key, le[0]),
                                   first + le[1])
            return _draw(k, name, shape[2:], dtype)

        le = jnp.stack(jnp.meshgrid(jnp.arange(L), jnp.arange(n),
                                    indexing="ij"), -1).reshape(L * n, 2)
        return jax.lax.map(one, le).reshape(shape)
    if len(shape) > 2:                # stacked layers, one at a time
        return jax.lax.map(
            lambda l: _draw(jax.random.fold_in(key, l), name, shape[1:],
                            dtype), jnp.arange(shape[0]))
    return _draw(key, name, shape, dtype)


def make(seed: int, c: dict, dtype=jnp.bfloat16) -> dict:
    key, (first, _) = seed_key(seed), share(c)
    return {name: _leaf(jax.random.fold_in(key, i), name, shape,
                        jnp.dtype(dtype).name, first)
            for i, (name, shape) in enumerate(sorted(shapes(c).items()))}
