"""Functional AdamW with global-norm clipping and cosine schedule.

Optimizer state dtype is configurable (``RunConfig.opt_dtype``): the largest
assigned archs (grok-1, qwen-110b, internvl-76b) use bf16 moments to fit the
v5e HBM budget (see DESIGN.md §6 / EXPERIMENTS.md memory table).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    mu: object     # pytree like params
    nu: object
    count: jax.Array


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    dtype: str = "float32"


def init(params, cfg: AdamConfig) -> AdamState:
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    z = lambda p: jnp.zeros(p.shape, dt)
    return AdamState(
        mu=jax.tree.map(z, params),
        nu=jax.tree.map(z, params),
        count=jnp.zeros((), jnp.int32),
    )


def schedule(cfg: AdamConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


# the global norm, the clip and the update, scoped for the benchmark's
# device trace (adamw_ms.train)
@jax.named_scope("adamw_update")
def update(grads, state: AdamState, params, cfg: AdamConfig
           ) -> Tuple[object, AdamState]:
    count = state.count + 1
    lr = schedule(cfg, count)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32, v32 = m.astype(jnp.float32), v.astype(jnp.float32)
        m_new = cfg.b1 * m32 + (1 - cfg.b1) * g
        v_new = cfg.b2 * v32 + (1 - cfg.b2) * g * g
        mhat = m_new / (1 - cfg.b1 ** count.astype(jnp.float32))
        vhat = v_new / (1 - cfg.b2 ** count.astype(jnp.float32))
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        decay = cfg.weight_decay if p.ndim >= 2 else 0.0
        p_new = p.astype(jnp.float32) * (1 - lr * decay) - lr * step
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    out = jax.tree.map(upd, params, grads, state.mu, state.nu)
    # leaves are plain tuples; NamedTuple params nodes are not (type check)
    is_triple = lambda x: type(x) is tuple
    p_new = jax.tree.map(lambda t: t[0], out, is_leaf=is_triple)
    mu = jax.tree.map(lambda t: t[1], out, is_leaf=is_triple)
    nu = jax.tree.map(lambda t: t[2], out, is_leaf=is_triple)
    return p_new, AdamState(mu=mu, nu=nu, count=count)
