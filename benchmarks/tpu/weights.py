"""Random weights of a dense decoder, made on the device from the seed.

One jitted call makes every leaf in the type it is served or trained in.
The layout is a plain dict (layers stacked on a leading axis), shared by the
drivers, which hand the same arrays to the program, and by the plain
reference, which reads them as they are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(c: dict) -> dict:
    d, ff, H, KV, hd, L, V = counts.dims(c)
    return {
        "embed": (V, d), "unembed": (d, V), "final_norm": (d,),
        "ln1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
        "wv": (L, d, KV * hd), "wo": (L, H * hd, d), "ln2": (L, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
    }


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name in ("final_norm", "ln1", "ln2"):
        return 0.1                      # gains scattered around 1
    return shape[-2] ** -0.5            # fan-in of the matmul


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _make(key, cfg_items, dtype):
    c = dict(cfg_items)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * _std(name, shape)
        if name in ("final_norm", "ln1", "ln2"):
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def make(seed: int, c: dict, dtype=jnp.bfloat16) -> dict:
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str, bool))))
    return _make(seed_key(seed), items, jnp.dtype(dtype).name)
