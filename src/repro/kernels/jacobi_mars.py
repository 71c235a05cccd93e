"""Pallas TPU kernel: chunked jacobi-1d with irredundant inter-tile carry.

The paper's §4 macro-pipeline (read MARS -> execute tile -> write MARS) maps
onto a sequential Pallas grid.  The field is viewed as ``(cells/128, 128)``
f32, one row per 128 cells in flat order, which is a bitcast of the 1-D
array's TPU layout (no relayout copy on either side of the kernel).  Each
grid step DMAs one block of ``rows`` x 128 cells HBM->VMEM — a whole number
of the paper's tiles of ``width`` cells, about ``BLOCK_BYTES`` — advances it
``T`` time steps, and writes the block's outputs back; the BlockSpec
pipeline double-buffers the DMAs.  Rows per block come from the field's
length and ``width`` alone (``block_rows``).

The inter-tile dataflow — the MARS — is the 2 cells x T time levels that a
tile's left edge needs from its predecessor.  It is never re-read from HBM
and never recomputed: the transfer is *irredundant*, exactly the paper's
property, where a conventional overlapped (trapezoidal) tiling would re-read
and recompute a T-wide halo per tile.  It rides in two places:

* between rows (and so between tiles) inside a block, in vector registers:
  the one-cell shift in flat order is a lane roll, with lanes 0-1 taken
  from the row above through a sublane roll;
* between blocks, in a ``(T, 128)`` VMEM scratch (the on-chip FIFO of
  Fig. 4/8): row s holds the previous block's last row at time level s,
  which the next block's row 0 reads as its row above.

Skewed geometry: at time level s (0-based input = s=0), flat position p
holds cell p - s, so a step needs the two positions before p at level s-1
and reuses its own.  Consequently position p of the result holds cell
p - T at time T; the wrapper in ops.py shifts indices and handles the
global boundary strip.  Each update is ``((v[p-2] + v[p-1]) + v[p]) / 3``
in f32, the reference's order.

Boundary contract (matches kernels/ref.py::jacobi_chunked_ref): edge values
are replicated, i.e. cell 0 and n-1 see a clamped neighbourhood.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: bytes of one grid step's input block (its output block is as large)
BLOCK_BYTES = 1 << 20


def block_rows(cells: int, width: int) -> int:
    """Rows of 128 cells per grid step for a field of ``cells`` cells.

    A block is a whole number of tiles of ``width`` cells and of (8, 128)
    f32 register tiles, at most ``BLOCK_BYTES``, and no larger than the
    field needs.
    """
    assert width % LANES == 0, width
    align = math.lcm(8, width // LANES)
    most = max(align, BLOCK_BYTES // (LANES * 4) // align * align)
    need = -(-cells // (LANES * align)) * align
    return min(most, need)


def grid_steps(cells: int, width: int) -> int:
    """Grid steps of one ``jacobi_chunked`` call over ``cells`` cells."""
    return cells // (block_rows(cells, width) * LANES)


def _kernel(x_ref, y_ref, carry_ref, *, t_steps: int):
    v = x_ref[...]                                    # (rows, 128) at level 0

    @pl.when(pl.program_id(0) == 0)
    def _init_carry():
        # ghost region left of cell 0 = replicated edge value; jacobi of a
        # constant is constant, so the ghost stays x[0] at every time level.
        carry_ref[...] = jnp.full(carry_ref.shape, v[0, 0], dtype=v.dtype)

    first_row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) == 0
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)

    def shifted(a, prev, k):
        """v[p-k] in flat order from a = v rolled by k lanes: its first k
        lanes come from the row above (a sublane roll; row 0's from prev)."""
        above = jnp.where(first_row, pltpu.roll(prev, k, 1),
                          pltpu.roll(a, 1, 0))
        return jnp.where(lane >= k, a, above)

    for s in range(t_steps):
        prev = carry_ref[s:s + 1, :]          # previous block's last row
        carry_ref[s:s + 1, :] = v[-1:, :]     # MARS out -> next block
        a1, a2 = pltpu.roll(v, 1, 1), pltpu.roll(v, 2, 1)
        v = ((shifted(a2, prev, 2) + shifted(a1, prev, 1)) + v) / 3.0

    y_ref[...] = v                                    # cells p - T at time T


@functools.partial(jax.jit, static_argnames=("t_steps", "width", "interpret"))
def jacobi_chunked(x: jax.Array, *, t_steps: int, width: int = 512,
                   interpret: bool = False) -> jax.Array:
    """T jacobi steps over [n] f32; returns the *skewed* output buffer.

    y[p] = value of cell (p - T) at time T.  ``n`` must be a whole number of
    blocks (``block_rows(n, width)`` x 128 cells).  Use ops.jacobi1d_tiled
    for the user-facing unskewed version.
    """
    n = x.shape[0]
    rows = block_rows(n, width)
    assert n % (rows * LANES) == 0, (n, rows)
    x2 = x.reshape(n // LANES, LANES).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, t_steps=t_steps),
        grid=(grid_steps(n, width),),
        in_specs=[pl.BlockSpec((rows, LANES), lambda c: (c, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((t_steps, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2)
    return out.reshape(n)
