"""Public jit'd wrappers around the Pallas kernels (with jnp fallbacks).

``use_pallas`` controls the backend: "auto" picks Pallas on TPU and the pure
jnp oracle elsewhere (this CPU container validates kernels via
interpret=True in tests; production traffic on CPU hosts shouldn't pay the
interpreter cost).

Every entry point is a *host-side* wrapper around the jitted kernel call,
so it can publish per-kernel ``repro.obs`` series without recording inside
a trace (the PR-6 rule): ``kernels/hbm_bytes{kernel=,dir=}`` and
``kernels/beats{kernel=,dir=}`` are computed analytically from the operand
shapes (what a roofline model charges the kernel: read every input once,
write every output once), ``kernels/calls`` counts invocations, and a
``kernels/<name>`` span brackets the dispatch.  When an entry point is
reached *inside* someone else's trace (operands are tracers), recording is
skipped entirely — trace-time counters would fire once per compile, not
once per call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import instrument as obs

from . import bitplane, jacobi_mars, kvpack, ref

#: analytic HBM transaction beat, bytes (256-bit bus word) — the logical
#: unit ``kernels/beats`` counts; deterministic, not a measured quantity
BEAT_BYTES = 32


# ---------------------------------------------------------------------------
# Analytic I/O models (read every input once, write every output once) —
# what the ``_record`` instrumentation below counts.
# ---------------------------------------------------------------------------

def pack_io_bytes(n: int, block: int, bits: int):
    """(read, write) bytes for pack_codes: s32 codes -> u32 bitplanes."""
    return n * block * 4, n * (block // 32 * bits) * 4


def unpack_io_bytes(n: int, block: int, bits: int):
    """(read, write) bytes for unpack_codes (pack's mirror)."""
    w, r = pack_io_bytes(n, block, bits)
    return r, w


def kv_quant_io_bytes(rows: int, d: int, bits: int, itemsize: int = 4):
    """(read, write) bytes for kv_quant: x -> (packed codes, f32 scales)."""
    cd = d if bits == 8 else d // 2
    return rows * d * itemsize, rows * cd + rows * 4


def kv_dequant_io_bytes(rows: int, d: int, bits: int):
    """(read, write) bytes for kv_dequant: (codes, scales) -> f32 values."""
    r, w = kv_quant_io_bytes(rows, d, bits)
    return w, rows * d * 4


def jacobi_io_bytes(n: int):
    """(read, write) bytes for jacobi1d: each f32 cell read/written once."""
    return n * 4, n * 4


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(use_pallas: str | bool) -> str:
    if use_pallas == "auto":
        return "pallas" if _on_tpu() else "ref"
    if use_pallas in (True, "pallas"):
        return "pallas"
    if use_pallas in ("interpret",):
        return "interpret"
    return "ref"


def _traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def _record(kernel: str, mode: str, read_bytes: int, write_bytes: int,
            **labels) -> None:
    """Publish the analytic traffic of one kernel dispatch (host side)."""
    if not obs.enabled():
        return
    obs.counter_inc("kernels/calls", 1, kernel=kernel, mode=mode, **labels)
    for d, nbytes in (("read", read_bytes), ("write", write_bytes)):
        obs.counter_inc("kernels/hbm_bytes", int(nbytes), kernel=kernel,
                        mode=mode, dir=d, **labels)
        obs.counter_inc("kernels/beats", -(-int(nbytes) // BEAT_BYTES),
                        kernel=kernel, mode=mode, dir=d, **labels)


# ---------------------------------------------------------------------------
# delta+bitplane codec
# ---------------------------------------------------------------------------

def pack_codes(q: jax.Array, bits: int, use_pallas: str | bool = "auto") -> jax.Array:
    """int32 codes [N, block] -> uint32 planes [N, block//32*bits]."""
    n, block = q.shape
    m = _mode(use_pallas)
    record = not _traced(q)
    with obs.span("kernels/pack", mode=m, bits=bits):
        if m == "ref":
            out = ref.pack_ref(q, bits)
        else:
            out = bitplane.pack(q, bits=bits, block=block,
                                interpret=(m == "interpret"))
    if record:
        _record("pack", m, *pack_io_bytes(n, block, bits), bits=bits)
    return out


def unpack_codes(planes: jax.Array, bits: int, block: int,
                 use_pallas: str | bool = "auto") -> jax.Array:
    m = _mode(use_pallas)
    record = not _traced(planes)
    with obs.span("kernels/unpack", mode=m, bits=bits):
        if m == "ref":
            out = ref.unpack_ref(planes, bits, block)
        else:
            out = bitplane.unpack(planes, bits=bits, block=block,
                                  interpret=(m == "interpret"))
    if record:
        n = planes.shape[0]
        _record("unpack", m, *unpack_io_bytes(n, block, bits), bits=bits)
    return out


# ---------------------------------------------------------------------------
# KV block packing
# ---------------------------------------------------------------------------

def kv_quant(x: jax.Array, bits: int = 8, use_pallas: str | bool = "auto"):
    m = _mode(use_pallas)
    record = not _traced(x)
    with obs.span("kernels/kv_quant", mode=m, bits=bits):
        if m == "ref":
            out = ref.kv_quant_ref(x, bits)
        else:
            out = kvpack.kv_quant(x, bits=bits, interpret=(m == "interpret"))
    if record:
        rows, d = x.shape
        _record("kv_quant", m,
                *kv_quant_io_bytes(rows, d, bits, x.dtype.itemsize),
                bits=bits)
    return out


def kv_dequant(codes: jax.Array, scales: jax.Array, bits: int = 8,
               use_pallas: str | bool = "auto") -> jax.Array:
    m = _mode(use_pallas)
    record = not _traced(codes, scales)
    with obs.span("kernels/kv_dequant", mode=m, bits=bits):
        if m == "ref":
            out = ref.kv_dequant_ref(codes, scales, bits)
        else:
            out = kvpack.kv_dequant(codes, scales, bits=bits,
                                    interpret=(m == "interpret"))
    if record:
        _record("kv_dequant", m,
                *kv_dequant_io_bytes(codes.shape[0], out.shape[-1], bits),
                bits=bits)
    return out


# ---------------------------------------------------------------------------
# Chunked jacobi (stencil macro-pipeline demo)
# ---------------------------------------------------------------------------

def _jacobi_padded_cells(n: int, t_steps: int, width: int) -> int:
    """Cells the kernel runs over: a ghost tile, the field, at least T edge
    cells, rounded up to whole blocks."""
    least = width + n + t_steps
    block = jacobi_mars.block_rows(least, width) * jacobi_mars.LANES
    return -(-least // block) * block


@functools.partial(jax.jit, static_argnames=("t_steps", "width", "use_pallas"))
def _jacobi1d_tiled_jit(x: jax.Array, t_steps: int, width: int,
                        use_pallas: str | bool) -> jax.Array:
    m = _mode(use_pallas)
    if m == "ref":
        return ref.jacobi_chunked_ref(x, t_steps)
    n = x.shape[0]
    assert t_steps < width - 2, (t_steps, width)
    pad_right = _jacobi_padded_cells(n, t_steps, width) - n - width
    xp = jnp.concatenate([
        jnp.full((width,), x[0], dtype=jnp.float32),
        x.astype(jnp.float32),
        jnp.full((pad_right,), x[-1], dtype=jnp.float32),
    ])
    ybuf = jacobi_mars.jacobi_chunked(xp, t_steps=t_steps, width=width,
                                      interpret=(m == "interpret"))
    return jax.lax.dynamic_slice(ybuf, (width + t_steps,), (n,))


def jacobi1d_tiled(x: jax.Array, t_steps: int, width: int = 512,
                   use_pallas: str | bool = "auto") -> jax.Array:
    """T jacobi steps (edge-padded open-boundary contract), chunked execution.

    The kernel runs over a padded domain: one full ghost tile of x[0] on the
    left (so the first real tile's carry is exact — the frozen far-left
    carry sits > width-T cells from any real cell) and edge padding on the
    right, at least T cells and up to a whole number of the kernel's blocks
    (the paper's 'partial tiles on host' become constant ghost regions
    here).  Kernel output position p holds cell p - T of the padded
    domain; real cell m lives at ybuf[m + width + T].

    HBM accounting charges the irredundant scheme: each cell is read once
    and written once per pass regardless of T, the carry riding in VMEM
    scratch (vs overlapped tiling's T-wide halo re-reads — see
    benchmarks/bench_stencil_kernel.py for the comparison model).
    """
    m = _mode(use_pallas)
    record = not _traced(x)
    with obs.span("kernels/jacobi1d", mode=m, t_steps=t_steps, width=width):
        out = _jacobi1d_tiled_jit(x, t_steps, width, use_pallas)
    if record:
        n = x.shape[0]
        grid = {} if m == "ref" else {"grid_steps": jacobi_mars.grid_steps(
            _jacobi_padded_cells(n, t_steps, width), width)}
        _record("jacobi1d", m, *jacobi_io_bytes(n), t_steps=t_steps, **grid)
    return out
