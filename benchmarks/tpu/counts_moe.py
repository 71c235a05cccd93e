"""Operations and bytes of a MoE decoder's grouped expert matmuls, from
their shapes (the yardstick of ``expert_roofline``).  Configurations are
the JSON dicts under ``configs/`` (Hugging Face key names), as in
``counts.py``: the work the algorithm needs, not what the program happens
to do, so a share computed from these counts cannot pass 100% unless the
time is wrong.
"""
from __future__ import annotations

import counts
from counts import dims
from weights_moe import share


def held(c: dict) -> int:
    """Experts this chip holds of each layer."""
    return share(c)[1]


def routed_rows(c: dict, tokens: int) -> int:
    """(token, expert) rows routed to the held experts of one layer.  Exact
    where every expert is held; for a share, its part under even
    routing."""
    E, k = c["num_local_experts"], c["num_experts_per_tok"]
    return tokens * k * held(c) // E


def expert_step(c: dict, tokens: int, weight_bytes: int = 2):
    """(FLOPs, bytes) of one step's grouped matmuls over every layer, for
    ``tokens`` tokens: each routed row costs 2 d ff for each of the gate,
    up and down projections (6 d ff); each held expert's three weights are
    read once (every held expert is taken to receive rows, as each does
    at a decode batch of hundreds of tokens), and each kernel reads its
    rows and writes its result once."""
    d, ff, _, _, _, L, _ = dims(c)
    rows = routed_rows(c, tokens)
    flops = 6.0 * d * ff * rows * L
    weights = 3.0 * held(c) * d * ff * weight_bytes
    io = rows * (3 * d + 3 * ff) * weight_bytes   # gate, up: d in, ff out;
    return flops, L * (weights + io)               # down: ff in, d out


def decode_step(c: dict, batch: int, ctx: int, kv_bits: int,
                weight_bytes: int = 2):
    """(FLOPs, bytes) of one decode step of the whole MoE decoder, as
    ``counts.decode_step_flops`` and ``counts.decode_step_bytes`` count a
    dense one (``ctx`` as each of them takes it), with each layer's dense
    MLP replaced by the router and the held experts: 2 d E FLOPs per token
    for the router and 6 d ff per routed row, the router's and each held
    expert's weights read once (every held expert receives rows at a
    decode batch of hundreds of tokens)."""
    d, ff, _, _, _, L, _ = dims(c)
    E = c["num_local_experts"]
    mlp, router = 3 * d * ff, d * E
    rows = routed_rows(c, batch)
    flops = (counts.decode_step_flops(c, batch, ctx)
             + L * (2.0 * batch * (router - mlp) + 6.0 * d * ff * rows))
    nbytes = (counts.decode_step_bytes(c, batch, ctx, kv_bits, weight_bytes)
              + L * weight_bytes * (router - mlp + held(c) * mlp))
    return flops, nbytes
