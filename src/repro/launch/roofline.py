"""Roofline analysis from the compiled dry-run artifact (no real hardware).

Terms, for the target chip named by its ``device_kind`` (see ``PEAKS``):
  compute    = FLOPs_per_device            / peak FLOP/s (bf16)
  memory     = bytes_accessed_per_device   / peak HBM B/s
  collective = collective_bytes_per_device / per-link ICI B/s

``cost_analysis()`` on the partitioned module reports per-device FLOPs/bytes;
collective bytes are parsed from the optimized HLO text (per-device shapes):
for each all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute instruction we count max(result bytes, operand bytes) —
one link traversal per byte; ring all-reduce costs ~2x which we annotate but
do not fold in (methodology note in EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from .hlo_text import (COLLECTIVE_OPS as _COLLECTIVES, SHAPE_RE as _SHAPE_RE,
                       shape_bytes as _shape_bytes)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # ICI bytes/s per link


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" is TPU v5e (Google Cloud documentation, "TPU v5e"):
#: 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI per chip over 4 links.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a chip missing from ``PEAKS`` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective op kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w\.\-]+\s*=\s*(.*)", line)
        if not m:
            continue
        rhs = m.group(1)
        for op in _COLLECTIVES:
            # match the op as the instruction name: "shape op(" or "(shape, ...) op("
            if re.search(rf"\)?\s{op}(-start|-done)?\(", " " + rhs):
                if f" {op}-done(" in " " + rhs:
                    continue  # counted at -start
                paren = rhs.index("(")
                result_part = rhs[:paren]
                operand_part = rhs[paren:]
                rbytes = sum(_shape_bytes(s)
                             for s in _SHAPE_RE.finditer(result_part))
                obytes = sum(_shape_bytes(s)
                             for s in _SHAPE_RE.finditer(operand_part))
                out[op] = out.get(op, 0) + max(rbytes, obytes)
                break
    return out


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    model_flops: float = 0.0       # 6*N*D (or 6*N_active*D)
    chips: int = 1
    peak_flops: float = 0.0        # per chip, of the target device kind

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_seconds(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def model_flops_ratio(self) -> Optional[float]:
        """MODEL_FLOPS / HLO_FLOPS (global): how much compiled compute is useful."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else None

    @property
    def mfu_bound(self) -> Optional[float]:
        """Achievable MFU if the dominant term were perfectly overlapped:
        useful model FLOPs / (chips * peak * bound_seconds)."""
        if not self.bound_seconds:
            return None
        return (self.model_flops
                / (self.chips * self.peak_flops * self.bound_seconds))


def analyze(flops_per_device: float, bytes_per_device: float,
            coll: Dict[str, int], chips: int, model_flops: float = 0.0,
            *, device_kind: str) -> Roofline:
    pk = peaks(device_kind)
    cb = float(sum(coll.values()))
    return Roofline(
        compute_s=flops_per_device / pk.flops,
        memory_s=bytes_per_device / pk.hbm_bw,
        collective_s=cb / pk.ici_bw,
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        coll_bytes_per_device=cb,
        model_flops=model_flops,
        chips=chips,
        peak_flops=pk.flops,
    )


def kernelized_io_bytes(cfg, rc, chips: int) -> float:
    """Per-device q/k/v/o (and SSD in/out) I/O of the fused TPU kernels.

    When the scoped interiors run as Pallas kernels, their HBM traffic is the
    kernel I/O: attention reads q,k,v and writes o once per layer per pass;
    SSD reads x,B,C,dt and writes y.  passes: train fwd + remat fwd + bwd
    reads ~= 4; prefill/decode 1.
    """
    passes = 4.0 if rc.kind == "train" else 1.0
    B, S = rc.global_batch, rc.seq_len
    if rc.kind == "decode":
        # fused dequant-attention kernel: reads the packed cache (codes +
        # scale markers) once per step per layer; SSM state reads are
        # unscoped (left in the general traffic count)
        if not cfg.n_heads:
            return 0.0
        s_cache = S if not cfg.sliding_window else min(S, cfg.sliding_window)
        bits = rc.kv_cache_bits
        per_pos = cfg.n_kv_heads * (cfg.hd * bits // 8
                                    + (4 if bits != 16 else 0))
        return cfg.n_layers * 2.0 * B * s_cache * per_pos / chips
    total = 0.0
    hd = cfg.hd if cfg.n_heads else 0
    attn_layers = 0
    if cfg.family in ("dense", "vlm", "moe", "hybrid"):
        attn_layers = cfg.n_layers
    elif cfg.family == "encdec":
        attn_layers = cfg.n_layers * 2 + cfg.enc_layers  # self+cross+enc
    if attn_layers and cfg.n_heads:
        qo = 2 * B * S * cfg.n_heads * hd
        kv = 2 * B * S * cfg.n_kv_heads * hd
        total += attn_layers * (qo + kv) * 2.0  # bf16
    if cfg.family in ("ssm", "hybrid"):
        di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        io = B * S * (2 * di + 2 * N + 2 * H) * 4.0
        total += cfg.n_layers * io
    return passes * total / chips


def model_flops_for(cfg, rc) -> float:
    """6*N*D per step (dense) / 6*N_active*D (MoE); decode: D = batch tokens."""
    n = cfg.active_param_count()
    if rc.kind == "train":
        tokens = rc.global_batch * rc.seq_len
        return 6.0 * n * tokens
    if rc.kind == "prefill":
        tokens = rc.global_batch * rc.seq_len
        return 2.0 * n * tokens          # forward only
    tokens = rc.global_batch              # one token per sequence
    return 2.0 * n * tokens
