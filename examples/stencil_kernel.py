"""The §4 macro-pipeline as a Pallas TPU kernel (interpret-mode demo).

Chunked jacobi-1d: each grid step DMAs a block of whole W-cell tiles
HBM->VMEM, advances it T time steps, carries the inter-tile MARS (2 cells x
T levels) through vector registers inside the block and VMEM scratch
between blocks — irredundant inter-tile dataflow, per the paper.

Run:  PYTHONPATH=src python examples/stencil_kernel.py
"""
import numpy as np

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

n, T, W = 1 << 15, 32, 512
x = jnp.asarray(np.cumsum(np.random.default_rng(0).uniform(-0.01, 0.01, n)),
                jnp.float32)

y_kernel = ops.jacobi1d_tiled(x, T, width=W, use_pallas="interpret")
y_ref = ref.jacobi_chunked_ref(x, T)
err = float(jnp.abs(y_kernel - y_ref).max())
print(f"jacobi1d chunked kernel: n={n} T={T} W={W}")
print(f"max |kernel - reference| = {err:.2e}")

halo_reads = (n // W) * 2 * T * 4
print(f"irredundant carry saves {halo_reads / 1e3:.1f} kB of halo re-reads "
      f"per pass vs overlapped tiling "
      f"({100 * halo_reads / (n * 4):.1f}% of the input)")
