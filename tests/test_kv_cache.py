"""The decode cache write (``layers.update_cache``) against the form it
replaced, a ``vmap`` of ``dynamic_update_slice`` per cache array: the same
cache, bit for bit, for every cache width, per-sequence positions, ring
slots and positions past a cache's end (which ``dynamic_update_slice``
clamps to its last row)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import layers

B, S, KV, D = 4, 8, 2, 16
CFG = base.ModelConfig(name="kv-test", family="dense", n_layers=1,
                       d_model=64, n_heads=4, n_kv_heads=KV, head_dim=D,
                       d_ff=128, vocab=64)


def _slice_form(cache, k_new, v_new, pos, bits):
    """The oracle: each sequence's row written by its own
    ``dynamic_update_slice``, batched by ``vmap``."""
    upd = jax.vmap(functools.partial(jax.lax.dynamic_update_slice_in_dim,
                                     axis=0))
    if bits == 16:
        return layers.KVCache(upd(cache.k, k_new.astype(cache.k.dtype), pos),
                              upd(cache.v, v_new.astype(cache.v.dtype), pos),
                              None, None)
    kq, ks = layers._quant_rows(k_new, bits)
    vq, vs = layers._quant_rows(v_new, bits)
    return layers.KVCache(upd(cache.k, kq, pos), upd(cache.v, vq, pos),
                          upd(cache.k_scale, ks, pos),
                          upd(cache.v_scale, vs, pos))


def _filled_cache(bits, seed=0):
    """A cache with a distinct value in every slot, so a row written to the
    wrong place, or a row left unwritten, shows."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    if bits == 16:
        def rnd(k):
            return jax.random.normal(k, (B, S, KV, D), jnp.bfloat16)
        return layers.KVCache(rnd(k1), rnd(k2), None, None)
    cd = D if bits == 8 else D // 2

    def codes(k):
        return jax.random.randint(k, (B, S, KV, cd), -128, 128, jnp.int32
                                  ).astype(jnp.int8)

    def scales(k):
        return jax.random.uniform(k, (B, S, KV, 1), jnp.float32, 0.5, 2.0)
    return layers.KVCache(codes(k1), codes(k2), scales(k3), scales(k4))


def _new_rows(seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (B, 1, KV, D), jnp.bfloat16) * 3,
            jax.random.normal(k2, (B, 1, KV, D), jnp.bfloat16))


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


POSITIONS = {
    "per_sequence": [0, S - 1, 3, 5],
    "ring_slots": [p % S for p in (S, 2 * S + 3, S - 1, 5 * S + 6)],
    "past_the_end": [S, S + 5, 0, 3 * S],
}


@pytest.mark.parametrize("bits", [16, 8, 4])
@pytest.mark.parametrize("case", sorted(POSITIONS))
def test_update_cache_matches_slice_form(bits, case):
    cache, (k_new, v_new) = _filled_cache(bits), _new_rows()
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    # both under jit: XLA may turn the quantizer's division by a constant
    # into a multiply, so a jitted and an eager scale can differ in the last bit
    got, want = (jax.jit(f, static_argnums=4)(cache, k_new, v_new, pos, bits)
                 for f in (layers.update_cache, _slice_form))
    _assert_same(got, want)


@pytest.mark.parametrize("bits", [16, 8, 4])
@pytest.mark.parametrize("window", [0, S])
def test_decode_attention_cache_matches_slice_form(bits, window,
                                                   monkeypatch):
    """Through ``decode_attention``: with a window as long as the cache it
    is a ring buffer (slot ``pos % S``), without one a position past the end
    lands on the last row."""
    p = layers.init_attn(jax.random.PRNGKey(2), CFG, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, 1, CFG.d_model),
                          jnp.bfloat16)
    cache = _filled_cache(bits)
    pos = jnp.asarray([0, S - 1, S + 2, 3 * S + 5], jnp.int32)

    def run():       # a new function each time, so jit traces it anew
        return jax.jit(lambda x, cache, pos: layers.decode_attention(
            x, p, CFG, cache, pos, bits, window))(x, cache, pos)
    out, got = run()
    monkeypatch.setattr(layers, "update_cache", _slice_form)
    want_out, want = run()
    _assert_same(got, want)
    _assert_same(out, want_out)
