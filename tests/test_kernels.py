"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.kernels import jacobi_mars, ops, ref


@pytest.mark.parametrize("bits", [4, 6, 8, 12, 16])
# 136 and 256 rows run two 128-row tiles (136 padded to 256)
@pytest.mark.parametrize("n,block", [(8, 128), (16, 256), (32, 512), (136, 128),
                                     (256, 256)])
def test_bitplane_pack_unpack_sweep(bits, n, block):
    rng = np.random.default_rng(bits * n)
    lim = max(1 << (bits - 2), 1)
    d = rng.integers(-lim // 2 - 1, lim // 2 + 1, size=(n, block)).astype(np.int32)
    q = np.cumsum(d, axis=1, dtype=np.int32)
    qj = jnp.asarray(q)
    p_ref = ref.pack_ref(qj, bits)
    p_int = ops.pack_codes(qj, bits, use_pallas="interpret")
    assert np.array_equal(np.asarray(p_ref), np.asarray(p_int))
    u_int = ops.unpack_codes(p_int, bits, block, use_pallas="interpret")
    assert np.array_equal(np.asarray(u_int), q)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("rows,d", [(8, 128), (32, 128), (16, 256), (136, 128),
                                    (256, 128)])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_kv_quant_sweep(bits, rows, d, dtype):
    rng = np.random.default_rng(rows)
    x = rng.standard_normal((rows, d)).astype(np.float32)
    xj = jnp.asarray(x, dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    c_ref, s_ref = ref.kv_quant_ref(xj, bits)
    c_int, s_int = ops.kv_quant(xj, bits, use_pallas="interpret")
    assert np.allclose(np.asarray(s_ref), np.asarray(s_int), rtol=1e-6)
    # compare through dequantization: 1-ulp scale differences may flip
    # round-half ties, so allow up to one quantization step on <1% of entries
    y_ref = np.asarray(ref.kv_dequant_ref(c_ref, s_ref, bits))
    y_int = np.asarray(ops.kv_dequant(c_int, s_int, bits, use_pallas="interpret"))
    step = np.asarray(s_ref)  # (rows, 1): one code step in value space
    d = np.abs(y_ref - y_int)
    assert (d <= step + 1e-6).all(), d.max()
    assert (d > 1e-6 * np.maximum(step, 1)).mean() < 0.01
    # quantization error bound vs the true input
    xf = np.asarray(xj, dtype=np.float32)
    qstep = np.abs(xf).max(axis=1) / (2 ** (bits - 1) - 1)
    assert (np.abs(y_ref - xf).max(axis=1) <= qstep + 1e-5).all()


# cells per grid step (2048 rows of 128); the last three cases cross block
# boundaries: 3 grid steps each, the last one of (8, 512, ...) all padding,
# that of (16, 1024, ...) mostly padding
BLOCK = jacobi_mars.BLOCK_BYTES // 4


@pytest.mark.parametrize("t_steps,width,n", [
    (4, 256, 1024), (16, 512, 2048), (63, 128, 1024), (8, 1024, 4096),
    (8, 512, 2 * BLOCK - 512), (16, 1024, 2 * BLOCK + 5000),
    (63, 128, 2 * BLOCK + 1000)])
def test_jacobi_chunked_sweep(t_steps, width, n):
    rng = np.random.default_rng(n)
    x = rng.standard_normal(n).astype(np.float32)
    y_ref = np.asarray(ref.jacobi_chunked_ref(jnp.asarray(x), t_steps))
    y_int = np.asarray(ops.jacobi1d_tiled(jnp.asarray(x), t_steps, width=width,
                                          use_pallas="interpret"))
    assert np.abs(y_ref - y_int).max() < 1e-5


@pytest.mark.parametrize("n,t_steps,width,steps", [
    (1024, 63, 128, 1), (2 * BLOCK - 512, 8, 512, 3),
    (2 * BLOCK - 520, 8, 512, 2), (2 * BLOCK + 5000, 16, 1024, 3),
    (1 << 29, 8, 512, 2049)])
def test_jacobi_grid_steps(n, t_steps, width, steps):
    padded = ops._jacobi_padded_cells(n, t_steps, width)
    assert padded >= width + n + t_steps
    assert jacobi_mars.grid_steps(padded, width) == steps
    assert padded == steps * jacobi_mars.block_rows(padded, width) * 128


def test_jacobi_grid_steps_counter():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(BLOCK + 1),
                    jnp.float32)
    with obs.enabled_scope() as (reg, _):
        ops.jacobi1d_tiled(x, 8, width=512, use_pallas="interpret")
    assert reg.counter_value("kernels/calls", kernel="jacobi1d",
                             mode="interpret", t_steps=8, grid_steps=2) == 1


def test_ops_ref_fallback_matches_interpret():
    rng = np.random.default_rng(0)
    q = np.cumsum(rng.integers(-3, 4, size=(8, 256)), axis=1).astype(np.int32)
    a = ops.pack_codes(jnp.asarray(q), 6, use_pallas="ref")
    b = ops.pack_codes(jnp.asarray(q), 6, use_pallas="interpret")
    assert np.array_equal(np.asarray(a), np.asarray(b))
