"""Pallas TPU kernel: chunked jacobi-1d with irredundant inter-tile carry.

The paper's §4 macro-pipeline (read MARS -> execute tile -> write MARS) maps
onto a sequential Pallas grid: each grid step DMAs one space chunk HBM->VMEM,
advances it ``T`` time steps, and writes the chunk's outputs back.  The
inter-tile dataflow — the MARS — is the 2 columns x T time-levels that each
chunk's left edge needs from its predecessor; it is carried through a VMEM
scratch buffer (the on-chip FIFO of Fig. 4/8) so it is never re-read from
HBM and never recomputed: the transfer is *irredundant*, exactly the paper's
property, where a conventional overlapped (trapezoidal) tiling would re-read
and recompute a T-wide halo per chunk.

Skewed chunk geometry: at time level s (0-based input = s=0), grid step c
holds values for cells [cW - s, (c+1)W - s).  Stepping needs two extra left
columns (from the carry) and reuses its own right edge.  Consequently output
block c of the result buffer holds cells [cW - T, (c+1)W - T) at time T; the
wrapper in ops.py shifts indices and handles the global boundary strip.

Boundary contract (matches kernels/ref.py::jacobi_chunked_ref): edge values
are replicated, i.e. cell 0 and n-1 see a clamped neighbourhood.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, y_ref, carry_ref, *, t_steps: int, width: int):
    c = pl.program_id(0)
    v = x_ref[...]                                    # (1, W) cells [cW,(c+1)W)

    @pl.when(c == 0)
    def _init_carry():
        # ghost region left of cell 0 = replicated edge value; jacobi of a
        # constant is constant, so the ghost stays x[0] at every time level.
        carry_ref[...] = jnp.full((t_steps, 2), v[0, 0], dtype=v.dtype)

    for s in range(1, t_steps + 1):
        left2 = carry_ref[s - 1, :].reshape(1, 2)     # cells [cW-s-1, cW-s+1)
        carry_ref[s - 1, :] = v[0, -2:]               # MARS out -> next chunk
        ext = jnp.concatenate([left2, v], axis=1)     # (1, W+2)
        v = (ext[:, :-2] + ext[:, 1:-1] + ext[:, 2:]) / 3.0

    y_ref[...] = v                                    # cells [cW-T,(c+1)W-T)


@functools.partial(jax.jit, static_argnames=("t_steps", "width", "interpret"))
def jacobi_chunked(x: jax.Array, *, t_steps: int, width: int = 512,
                   interpret: bool = False) -> jax.Array:
    """T jacobi steps over [n] f32; returns the *skewed* output buffer.

    y[c*W + k] = value of cell (c*W - T + k) at time T.  Use
    ops.jacobi1d_tiled for the user-facing unskewed version.
    """
    n = x.shape[0]
    assert n % width == 0, (n, width)
    assert t_steps < width - 2, "carry depth must fit one chunk"
    x2 = x.reshape(1, n).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, t_steps=t_steps, width=width),
        grid=(n // width,),
        in_specs=[pl.BlockSpec((1, width), lambda c: (0, c))],
        out_specs=pl.BlockSpec((1, width), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t_steps, 2), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2)
    return out.reshape(n)
