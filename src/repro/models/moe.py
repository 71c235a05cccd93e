"""Top-k MoE block (mixtral / grok): dropless, grouped by expert.

The router scores every token against all ``n_experts`` experts (f32
softmax over the logits, top-k of the probabilities, the k weights
renormalized to sum to one, as Mixtral routes).  The layer holds a share
of the experts, ``ModelConfig.held_experts`` = (first, count): its
parameters carry those ``count`` experts only, and it computes the part of
the result that they give.  Routed (token, expert) rows are sorted by
expert, and each held expert runs its SwiGLU on exactly the rows routed to
it through a grouped matmul (``jax.lax.ragged_dot``; on a TPU a Mosaic
kernel that reads each routed expert's weights once and runs a group over
whole row tiles: at a decode batch, one tile of all the rows).  No token
is dropped, whatever the routing.  Rows routed to
an expert this share does not hold contribute nothing here: in an
expert-parallel deployment another chip's share gives them, and the shares'
results add up to the whole layer.

Device scopes for the benchmark's trace: ``moe_route`` (router, top-k,
grouping and the weighted combine) and ``moe_experts`` (the grouped
matmuls).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

F32 = jnp.float32


class MoeParams(NamedTuple):
    router: jax.Array      # (d, n_experts): scores every expert
    w_gate: jax.Array      # (count, d, ff): the held experts only
    w_up: jax.Array        # (count, d, ff)
    w_down: jax.Array      # (count, ff, d)


def init_moe(key, cfg: ModelConfig, dtype) -> MoeParams:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    _, count = cfg.held_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return MoeParams(
        router=(jax.random.normal(k1, (d, E)) * s).astype(dtype),
        w_gate=(jax.random.normal(k2, (count, d, ff)) * s).astype(dtype),
        w_up=(jax.random.normal(k3, (count, d, ff)) * s).astype(dtype),
        w_down=(jax.random.normal(k4, (count, ff, d)) * ff ** -0.5
                ).astype(dtype),
    )


def moe_specs() -> MoeParams:
    return MoeParams(
        router=("fsdp", None),
        w_gate=("experts", "fsdp", "ff"),
        w_up=("experts", "fsdp", "ff"),
        w_down=("experts", "ff", "fsdp"),
    )


def moe_block(x: jax.Array, p: MoeParams, cfg: ModelConfig,
              group0: jax.Array | int = 0) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (this share's part of the output (B, S, d),
    load-balance aux loss over all experts).

    ``p.router`` is one layer's (d, n_experts).  The expert weights are a
    table of groups, (G, d, ff) and (G, ff, d), in which this layer's held
    experts are the ``count`` groups from ``group0``; the table's other
    groups get no rows.  One layer's parameters are such a table (G =
    count, ``group0`` 0).  A caller with every layer's experts stacked can
    hand them over as one table of L * count groups, so that no layer's
    weights are copied out of the stack (a slice cannot fuse into the
    grouped-matmul kernel)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.topk
    first, count = cfg.held_experts
    n = B * S
    xf = x.reshape(n, d)

    with jax.named_scope("moe_route"):
        probs = jax.nn.softmax(
            jnp.dot(xf, p.router, preferred_element_type=F32), axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)                 # (n, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        # aux load-balancing loss (Switch eq. 4/5)
        frac_tokens = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=F32),
                               axis=0)
        aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
        # rows (token, choice) sorted by held expert; rows for experts not
        # held here go last, past every group
        local = top_e.reshape(-1) - first                    # (n*k,)
        held = (local >= 0) & (local < count)
        group = jnp.where(held, local, count)
        order = jnp.argsort(group, stable=True)
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((p.w_gate.shape[0],), jnp.int32),
            jnp.sum(jax.nn.one_hot(group, count, dtype=jnp.int32), axis=0),
            (group0,))
        rows = jnp.take(xf, order // k, axis=0)              # (n*k, d)

    with jax.named_scope("moe_experts"):
        h = (jax.nn.silu(jax.lax.ragged_dot(rows, p.w_gate, sizes))
             * jax.lax.ragged_dot(rows, p.w_up, sizes))
        y = jax.lax.ragged_dot(h, p.w_down, sizes)           # (n*k, d)

    with jax.named_scope("moe_route"):
        back = jnp.argsort(order)                            # unsort
        y = jnp.take(y, back, axis=0).reshape(n, k, d)
        w = top_w.reshape(n, k, 1)
        # rows past the groups are left unwritten by the grouped matmul
        out = jnp.sum(jnp.where(held.reshape(n, k, 1), y.astype(F32) * w,
                                0.0), axis=1)
    return out.astype(x.dtype).reshape(B, S, d), aux
