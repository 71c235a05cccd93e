"""Compressed cross-pod gradient exchange (the paper's technique, DESIGN §3.1).

Data-parallel gradients crossing the pod boundary (slow inter-pod links) are
the framework's dominant "inter-tile dataflow".  Each pod's gradient shard is
an atomic, irredundant block; before the cross-pod exchange it is quantized
to ``bits`` two's-complement codes per value with a per-block scale (the
markers analogue) and bitplane-packed (kernels/bitplane, TPU form of §2.4
packing), cutting cross-pod bytes by ~32/bits vs f32 (16/bits vs bf16).

Sharding-preservation invariant: the codec blocks along the LAST tensor axis
in groups of 32 and never reshapes across leading axes — flattening a
(model/data)-sharded gradient would force SPMD to rematerialize it
replicated, multiplying within-pod traffic (measured; see EXPERIMENTS.md
§Perf Cell D).  Leaves whose last axis is not 32-divisible (tiny: norms,
per-head scalars) are exchanged raw with ``lax.pmean``.

Error feedback (residual carried per pod in the optimizer state) makes the
lossy quantization unbiased over time — the divergence from the paper's
lossless codec and its rationale are documented in DESIGN.md §2.

The train step realizes the exchange in pure auto-GSPMD (a vmap over a
pod-sharded leading axis — this XLA's SPMD partitioner aborts on while ops
inside manual subgroups, see train/step.py); the equivalent manual-'pod'
``shard_map`` spelling is compiled and byte-audited against
``ExchangeStats`` by ``repro.launch.audit``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import blockcodec as bc
from repro.obs import instrument as obs

F32 = jnp.float32
BLOCK = 32                 # values per scale block (= one bitplane group)
MIN_COMPRESS_SIZE = 4096   # smaller leaves go raw (scale overhead dominates)


def _quant_lastdim(x: jax.Array, bits: int):
    """(..., last) f32 -> (planes uint32 (..., nb, bits), scale (..., nb))."""
    *lead, last = x.shape
    xb = x.reshape(*lead, last // BLOCK, BLOCK)
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -qmax, qmax)
    planes = bc.bitplane_pack(q.astype(jnp.int32), bits)
    return planes, scale


def _dequant_lastdim(planes: jax.Array, scale: jax.Array, bits: int,
                     shape) -> jax.Array:
    q = bc.bitplane_unpack(planes, bits)
    x = q.astype(F32) * scale[..., None]
    return x.reshape(shape)


def compressible(g: jax.Array) -> bool:
    return g.size >= MIN_COMPRESS_SIZE and g.shape[-1] % BLOCK == 0


def quantize_tree(grads, resids, bits: int, axis_name: str = "pod"):
    """Pod-local half of the exchange (runs inside the manual-'pod' region).

    Compressible leaves -> (planes, scale, new_resid); small leaves are
    pod-pmean'd in place (their operands are replicated over data/model, the
    only in-manual collective shape the partitioner handles robustly).
    Returns (planes_tree, scales_tree, raw_means_tree, new_resids_tree) with
    None at non-applicable positions.
    """
    def one(g, r):
        if not compressible(g):
            mean = jax.lax.pmean(g.astype(F32), axis_name).astype(g.dtype)
            return (None, None, mean, jnp.zeros_like(r))
        x = g.astype(F32) + r
        planes, scale = _quant_lastdim(x, bits)
        new_resid = x - _dequant_lastdim(planes, scale, bits, x.shape)
        return (planes, scale, None, new_resid)

    out = jax.tree.map(one, grads, resids)
    is_q = lambda t: type(t) is tuple
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is_q)
    return pick(0), pick(1), pick(2), pick(3)


def dequant_mean_tree(grads_like, planes, scales, raw_means, bits: int,
                      n_pods: int):
    """Auto-GSPMD half: planes/scales arrive with a leading pod dim (sharded
    P('pod')); static per-pod indexing makes SPMD insert the cross-pod
    gathers of the *packed* data — the compressed wire.
    """
    def one(g, p, s, raw):
        if raw is not None:
            return raw
        total = None
        for i in range(n_pods):
            d = _dequant_lastdim(p[i], s[i], bits, g.shape)
            total = d if total is None else total + d
        return (total / n_pods).astype(g.dtype)

    return jax.tree.map(
        one, grads_like, planes, scales, raw_means,
        is_leaf=lambda x: x is None)


def init_residuals(params) -> object:
    """Error-feedback state: one f32 residual per param (pod-local)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)


def compressed_bytes_per_param(bits: int, block: int = BLOCK) -> float:
    """Wire bytes per parameter for the compressed exchange."""
    return bits / 8 + 4.0 / block


# ---------------------------------------------------------------------------
# Wire-byte accounting (host side — the exchange itself runs traced)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExchangeStats:
    """Analytic per-exchange wire accounting for one gradient pytree.

    ``quantize_tree``/``dequant_mean_tree`` execute inside traced SPMD
    regions where obs must not record (PR-6 rule), so the byte accounting
    is computed here from leaf shapes alone — exact, because the codec's
    output sizes are static functions of shape and ``bits`` — and published
    by the *caller* outside the jit boundary, once per exchange.
    """
    bits: int
    compressed_leaves: int
    raw_leaves: int
    raw_bytes: int          # what an uncompressed f32 exchange would move
    wire_bytes: int         # planes + scales, plus raw leaves verbatim

    @property
    def reduction(self) -> float:
        return self.raw_bytes / self.wire_bytes if self.wire_bytes else 0.0

    def publish(self, **labels) -> None:
        """Emit ``collectives/*`` series (no-op when obs is disabled)."""
        if not obs.enabled():
            return
        lb = dict(labels, bits=self.bits)
        obs.counter_inc("collectives/exchanges", 1, **lb)
        obs.counter_inc("collectives/raw_bytes", self.raw_bytes, **lb)
        obs.counter_inc("collectives/wire_bytes", self.wire_bytes, **lb)
        obs.counter_inc("collectives/leaves", self.compressed_leaves,
                        kind="compressed", **lb)
        obs.counter_inc("collectives/leaves", self.raw_leaves,
                        kind="raw_fallback", **lb)
        obs.gauge_set("collectives/reduction", self.reduction, **lb)


def exchange_stats(tree, bits: int) -> ExchangeStats:
    """Wire accounting for exchanging ``tree`` at ``bits`` (shapes only)."""
    compressed = raw = 0
    raw_bytes = wire_bytes = 0
    for g in jax.tree.leaves(tree):
        size = int(g.size)
        raw_bytes += size * 4
        if compressible(g):
            compressed += 1
            wire_bytes += size * bits // 8 + size // BLOCK * 4
        else:
            raw += 1
            wire_bytes += size * 4
    return ExchangeStats(bits=bits, compressed_leaves=compressed,
                         raw_leaves=raw, raw_bytes=raw_bytes,
                         wire_bytes=wire_bytes)
