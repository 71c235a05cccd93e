"""Plain float32 reference of training steps of the dense decoder.

The loss is the mean next-token cross-entropy of ``granite.hidden``'s
forward, at full matmul precision.  To fit one chip beside nothing else
it recomputes each layer in the backward pass and takes the LM head in
blocks of rows; neither changes the arithmetic.

AdamW, written from its description: gradients clipped to a global norm,
bias-corrected first and second moments, decoupled weight decay, a linear
warm-up followed by a cosine decay to a tenth of the peak rate.  The
configuration gives the hyper-parameters, and which leaves decay
(``decay_min_rank``: leaves of that rank or more as stored).

``two_steps`` runs the first two updates; the second moment of step one is
rebuilt from its gradient, which waits on the host, so that the chip holds
only parameters, one gradient and the activations.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import granite

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 1024             # LM-head rows per block


def _loss(w, tokens, labels, c):
    x = w["embed"][tokens]                                    # (B, S, d)

    @jax.checkpoint
    def body(x, lw):
        return granite.layer(x, lw, c), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in granite.LAYER_KEYS})
    x = granite.rmsnorm(x, w["final_norm"], c["rms_norm_eps"])
    B, S, d = x.shape
    rows = min(ROWS, B * S)
    xs = x.reshape(-1, rows, d)
    ls = labels.reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        xb, lb = args
        lg = jnp.matmul(xb, w["unembed"], precision=HIGHEST)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(block, (xs, ls))) / (B * S)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _value_and_grad(w, tokens, labels, cfg_items):
    return jax.value_and_grad(_loss)(w, tokens, labels, dict(cfg_items))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _value(w, tokens, labels, cfg_items):
    return _loss(w, tokens, labels, dict(cfg_items))


def loss_and_grads(w, batch, c):
    return _value_and_grad(w, jnp.asarray(batch["tokens"]),
                           jnp.asarray(batch["labels"]),
                           granite._config_items(c))


def loss(w, batch, c):
    return float(_value(w, jnp.asarray(batch["tokens"]),
                        jnp.asarray(batch["labels"]),
                        granite._config_items(c)))


def learning_rate(opt: dict, step: int) -> float:
    """Rate of update number ``step`` (1-based)."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


def global_norm(tree) -> float:
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(tree))))


@jax.jit
def _adam_leaf(p, g, m, v, scale, lr, decay, b1, b2, eps, t):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return p * (1 - lr * decay) - lr * mhat / (jnp.sqrt(vhat) + eps)


def two_steps(w0, batches, c, opt):
    """Losses of three batches around two AdamW updates.  ``w0`` (f32)
    is emptied as soon as it is no longer needed.

    Returns (losses[3], first clipped gradient as a dict of host arrays,
    parameters after two updates as device arrays)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    losses = []
    l1, g1 = loss_and_grads(w0, batches[0], c)
    losses.append(float(l1))
    s1 = min(1.0, opt["grad_clip"] / max(global_norm(g1), 1e-9))
    g1 = {k: np.asarray(v) * np.float32(s1) for k, v in g1.items()}   # host
    lr1 = learning_rate(opt, 1)
    ranks = {k: v.ndim for k, v in w0.items()}

    def decay(k):
        return opt["weight_decay"] if ranks[k] >= opt["decay_min_rank"] else 0.0

    zero = jnp.zeros((), F32)
    w1 = {k: _adam_leaf(w0[k], jnp.asarray(g1[k]), zero, zero, 1.0, lr1,
                        decay(k), b1, b2, eps, 1.0) for k in ranks}
    w0.clear()                      # the caller hands its copy over
    l2, g2 = loss_and_grads(w1, batches[1], c)
    losses.append(float(l2))
    s2 = min(1.0, opt["grad_clip"] / max(global_norm(g2), 1e-9))
    lr2 = learning_rate(opt, 2)
    w2 = {}
    for k in ranks:
        m1 = (1 - b1) * jnp.asarray(g1[k])
        v1 = (1 - b2) * jnp.square(jnp.asarray(g1[k]))
        w2[k] = _adam_leaf(w1[k], g2[k], m1, v1, s2, lr2, decay(k), b1, b2,
                           eps, 2.0)
        g2[k] = None
    del g2, w1
    losses.append(loss(w2, batches[2], c))
    return losses, g1, w2
