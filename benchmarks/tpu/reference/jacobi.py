"""Plain reference of a pass of the 1-D 3-point Jacobi stencil.

The boundary contract of ``ops.jacobi1d_tiled``: at the start of a pass the
field is extended on both sides with its edge values, then advanced ``T``
steps, each cell becoming the mean of itself and its two neighbours,
``(left + centre + right) / 3``; the ``n`` interior cells are returned.
Cells within ``T`` of an edge thus see the edge value held at its start-of-
pass level on the outside.  Computed in the dtype given (float32 for the
reference, lower for the control) and returned as float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("t_steps", "dtype"),
                   donate_argnums=(0,))
def one_pass(x, t_steps: int, dtype=jnp.float32):
    v = x.astype(dtype)
    v = jnp.concatenate([jnp.full((t_steps,), v[0], dtype), v,
                         jnp.full((t_steps,), v[-1], dtype)])
    three = jnp.asarray(3, dtype)
    for _ in range(t_steps):
        v = (v[:-2] + v[1:-1] + v[2:]) / three
    return v.astype(jnp.float32)


def passes(x, t_steps: int, n: int, dtype=jnp.float32):
    """``n`` passes, each pass's output the next one's input."""
    for _ in range(n):
        x = one_pass(x, t_steps, dtype)
    return x
