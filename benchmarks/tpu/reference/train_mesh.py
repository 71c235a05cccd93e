"""The plain float32 reference of ``reference/train.py``, over several chips.

The same mathematics: ``granite.layer``'s forward at full matmul
precision, the mean next-token cross-entropy with the LM head taken in
blocks of rows, and ``train.py``'s AdamW.  Only the layout differs, so that
a model whose f32 state does not fit one chip fits the chips of one host:
every leaf is split across the chips along one axis (``shardings``), each
layer's weights are gathered whole inside the layer loop, and the batch
goes through in groups of one sequence per chip, whose gradients are
summed.  These are reorderings of the same f32 sums.

``two_steps`` returns what ``train.two_steps`` returns, with the first
clipped gradient left on the chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference import granite, train

AXIS = "chips"


def make_mesh(devices) -> Mesh:
    return Mesh(np.asarray(devices), (AXIS,))


def shardings(shapes: dict, mesh: Mesh) -> dict:
    """For each leaf, a split along its largest axis that the chips divide
    (the leaf is copied whole to each chip where no axis is divided)."""
    n = mesh.size

    def one(shape):
        axes = [i for i in range(len(shape)) if shape[i] % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        split = max(axes, key=lambda i: shape[i])
        return NamedSharding(mesh, P(*[AXIS if i == split else None
                                       for i in range(len(shape))]))

    return {k: one(s) for k, s in shapes.items()}


def _loss(w, tokens, labels, c, mesh):
    whole = NamedSharding(mesh, P())
    seqs = NamedSharding(mesh, P(AXIS))
    x = jax.lax.with_sharding_constraint(w["embed"][tokens], seqs)

    @jax.checkpoint
    def body(x, lw):
        lw = jax.lax.with_sharding_constraint(lw, whole)
        return jax.lax.with_sharding_constraint(granite.layer(x, lw, c),
                                                seqs), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in granite.LAYER_KEYS})
    x = granite.rmsnorm(x, w["final_norm"], c["rms_norm_eps"])
    unembed = jax.lax.with_sharding_constraint(w["unembed"], whole)
    B, S, d = x.shape
    rows = min(train.ROWS, S)
    xs = jnp.moveaxis(x.reshape(B, S // rows, rows, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, S // rows, rows), 1, 0)

    @jax.checkpoint
    def block(args):
        xb, lb = args                                       # (B, rows, d)
        lg = jnp.matmul(xb, unembed, precision=train.HIGHEST)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lb[..., None], -1)[..., 0])

    return jnp.sum(jax.lax.map(block, (xs, ls))) / (B * S)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mesh"))
def _value_and_grad(w, tokens, labels, cfg_items, mesh):
    l, g = jax.value_and_grad(_loss)(w, tokens, labels, dict(cfg_items), mesh)
    return l, jax.lax.with_sharding_constraint(
        g, shardings({k: v.shape for k, v in g.items()}, mesh))


@functools.partial(jax.jit, static_argnames=("cfg_items", "mesh"))
def _value(w, tokens, labels, cfg_items, mesh):
    return _loss(w, tokens, labels, dict(cfg_items), mesh)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(tree, s):
    return jax.tree.map(lambda x: x * s, tree)


def _groups(batch, mesh):
    """The batch's (tokens, labels) in groups of one sequence per chip."""
    toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
    n = mesh.size
    if len(toks) % n:
        raise ValueError(f"a batch of {len(toks)} sequences does not split "
                         f"over {n} chips")
    seqs = NamedSharding(mesh, P(AXIS))
    return [(jax.device_put(toks[i:i + n], seqs),
             jax.device_put(labs[i:i + n], seqs))
            for i in range(0, len(toks), n)]


def loss_and_grads(w, batch, c, mesh):
    items = granite._config_items(c)
    total, grads = 0.0, None
    groups = _groups(batch, mesh)
    for toks, labs in groups:
        l, g = _value_and_grad(w, toks, labs, items, mesh)
        total += float(l)
        grads = g if grads is None else _add(grads, g)
    if len(groups) > 1:
        grads = _scale(grads, 1.0 / len(groups))
    return total / len(groups), grads


def loss(w, batch, c, mesh) -> float:
    items = granite._config_items(c)
    groups = _groups(batch, mesh)
    return sum(float(_value(w, t, lb, items, mesh))
               for t, lb in groups) / len(groups)


def two_steps(w0, batches, c, opt, mesh):
    """Losses of three batches around two AdamW updates, as
    ``train.two_steps``; ``w0`` (f32, in ``shardings``) is emptied as soon
    as it is no longer needed.

    Returns (losses[3], first clipped gradient, parameters after two
    updates), the last two as arrays on the chips."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    ranks = {k: v.ndim for k, v in w0.items()}

    def decay(k):
        return opt["weight_decay"] if ranks[k] >= opt["decay_min_rank"] else 0.0

    losses = []
    l1, g1 = loss_and_grads(w0, batches[0], c, mesh)
    losses.append(l1)
    g1 = _scale(g1, min(1.0, opt["grad_clip"]
                        / max(train.global_norm(g1), 1e-9)))
    lr1 = train.learning_rate(opt, 1)
    zero = jnp.zeros((), jnp.float32)
    w1 = {k: train._adam_leaf(w0[k], g1[k], zero, zero, 1.0, lr1, decay(k),
                              b1, b2, eps, 1.0) for k in ranks}
    w0.clear()                      # the caller hands its copy over
    l2, g2 = loss_and_grads(w1, batches[1], c, mesh)
    losses.append(l2)
    s2 = min(1.0, opt["grad_clip"] / max(train.global_norm(g2), 1e-9))
    lr2 = train.learning_rate(opt, 2)
    w2 = {}
    for k in ranks:
        w2[k] = train._adam_leaf(w1[k], g2[k], (1 - b1) * g1[k],
                                 (1 - b2) * jnp.square(g1[k]), s2, lr2,
                                 decay(k), b1, b2, eps, 2.0)
        g2[k] = w1[k] = None
    losses.append(loss(w2, batches[2], c, mesh))
    return losses, g1, w2
