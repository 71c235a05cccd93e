"""Operations and bytes that each piece of work needs, from its shapes.

The yardstick for the utilization and roofline metrics.  Configurations are
the JSON dicts under ``configs/`` (Hugging Face key names).  Every count is
of the work the algorithm needs, not of what the program happens to do:
recomputation, masked-out blocks and padding are not counted, so a share
computed from these counts cannot pass 100% unless the time is wrong.
"""
from __future__ import annotations


def dims(c: dict):
    """(d, ff, H, KV, hd, L, V) of a dense decoder configuration."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // H
    return (d, c["intermediate_size"], H, c["num_key_value_heads"], hd,
            c["num_hidden_layers"], c["vocab_size"])


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's matmuls: q, k, v, o and the SwiGLU MLP."""
    d, ff, H, KV, hd, _, _ = dims(c)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def matmul_params(c: dict) -> int:
    """Every layer's matmul weights plus the LM head.  The input embedding
    is a lookup, not a matmul, and norm gains are elementwise."""
    d, _, _, _, _, L, V = dims(c)
    return L * layer_matmul_params(c) + d * V


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one forward and backward pass over batch x seq tokens:
    6 N T for the matmuls, plus causal attention, 6 S^2 (H hd) per sequence
    and layer (QK^T and PV, half the square, three passes).  Recomputation
    under remat is not counted."""
    d, _, H, _, hd, L, _ = dims(c)
    return (6.0 * matmul_params(c) * batch * seq
            + 6.0 * seq * seq * H * hd * batch * L)


def decode_step_flops(c: dict, batch: int, ctx: int) -> float:
    """One decode step: every sequence is one token attending to ``ctx``
    cached positions (its own included)."""
    _, _, H, _, hd, L, _ = dims(c)
    return batch * (2.0 * matmul_params(c) + L * 4.0 * ctx * H * hd)


def kv_row_bytes(hd: int, kv_bits: int) -> float:
    """Bytes of one cached key or value row: codes, plus an f32 scale where
    the cache is packed."""
    return hd * kv_bits / 8 + (4 if kv_bits < 16 else 0)


def decode_step_bytes(c: dict, batch: int, ctx: int, kv_bits: int,
                      weight_bytes: int = 2) -> float:
    """Least HBM bytes of one decode step: every matmul weight read once,
    the batch's embedding rows, the ``ctx`` cached k/v rows of each
    sequence read once, and the new k/v row written."""
    d, _, _, KV, hd, L, _ = dims(c)
    row = kv_row_bytes(hd, kv_bits)
    return (weight_bytes * matmul_params(c) + batch * d * weight_bytes
            + batch * L * KV * 2 * row * (ctx + 1))


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: FLOPs or bytes, whichever binds."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bytes)


def causal_pairs(seq: int) -> float:
    """Query-key pairs under a causal mask."""
    return seq * (seq + 1) / 2.0


def flash_fwd(batch: int, seq: int, H: int, KV: int, hd: int,
              itemsize: int = 2):
    """(FLOPs, bytes) of the forward kernel: QK^T and PV over the causal
    pairs; reads q, k, v and writes o (``itemsize`` bytes per element, 2
    for bf16, 4 for f32) and the f32 log-sum-exp."""
    flops = 2 * 2.0 * causal_pairs(seq) * hd * H * batch
    q = batch * seq * H * hd * itemsize
    kv = batch * seq * KV * hd * itemsize
    return flops, 2 * q + 2 * kv + batch * H * seq * 4


def flash_bwd_dkv(batch: int, seq: int, H: int, KV: int, hd: int,
                  itemsize: int = 2):
    """(FLOPs, bytes) of the dk/dv kernel: it recomputes QK^T (it is handed
    no probabilities), then dP = dO V^T, dV = P^T dO and dK = dS^T Q."""
    flops = 4 * 2.0 * causal_pairs(seq) * hd * H * batch
    q = batch * seq * H * hd * itemsize
    kv = batch * seq * KV * hd * itemsize
    rows = batch * H * seq * 4
    return flops, 2 * q + 2 * kv + 2 * rows + 2 * kv


def flash_bwd_dq(batch: int, seq: int, H: int, KV: int, hd: int,
                 itemsize: int = 2):
    """(FLOPs, bytes) of the dq kernel: QK^T, dP = dO V^T and dQ = dS K."""
    flops = 3 * 2.0 * causal_pairs(seq) * hd * H * batch
    q = batch * seq * H * hd * itemsize
    kv = batch * seq * KV * hd * itemsize
    rows = batch * H * seq * 4
    return flops, 2 * q + 2 * kv + 2 * rows + q


def jacobi_pass_bytes(n: int) -> float:
    """Least bytes of one pass of T steps over n f32 cells: each cell read
    once and written once; the steps in between stay on chip."""
    return 8.0 * n
