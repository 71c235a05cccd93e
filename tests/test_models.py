"""Per-architecture smoke tests + decode-path consistency."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import base
from repro.models import model_zoo, transformer


def _batch_for(cfg, rc, seed=0):
    specs = model_zoo.input_specs(cfg, rc)
    rng = np.random.default_rng(seed)
    out = {}
    for k, s in specs.items():
        if s.dtype == jnp.int32:
            out[k] = jnp.asarray(
                rng.integers(0, cfg.vocab, size=s.shape, dtype=np.int32))
        else:
            out[k] = jnp.asarray(rng.standard_normal(s.shape), dtype=s.dtype)
    return out


@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one full train step, finite results."""
    cfg = base.load_smoke(arch)
    rc = base.RunConfig(seq_len=64, global_batch=2, kind="train", remat=False,
                        q_block=32, kv_block=32)
    api = model_zoo.get_api(cfg, rc)
    params = api.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, rc)
    loss = jax.jit(api.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    from repro.train import step as ts
    step = ts.make_train_step(api, cfg, rc, None)
    state = ts.init_state(api, rc, jax.random.PRNGKey(0))
    state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)))
    assert moved


@pytest.mark.parametrize("arch", base.ARCH_IDS)
@pytest.mark.parametrize("bits", [16, 8])
def test_smoke_decode(arch, bits):
    cfg = base.load_smoke(arch)
    rc = base.RunConfig(seq_len=96, global_batch=2, kind="decode", remat=False,
                        q_block=32, kv_block=32, kv_cache_bits=bits)
    api = model_zoo.get_api(cfg, rc)
    params = api.init(jax.random.PRNGKey(0))
    state = api.init_decode_state(2)
    step = jax.jit(api.decode_step)
    tok = jnp.array([1, 2], jnp.int32)
    for _ in range(4):
        lg, state = step(params, state, tok)
    assert lg.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(lg)).all()


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-130m",
                                  "hymba-1.5b"])
def test_decode_matches_teacher_forcing(arch):
    cfg = base.load_smoke(arch)
    rc = base.RunConfig(seq_len=32, global_batch=2, kind="decode", remat=False,
                        q_block=16, kv_block=16, param_dtype="float32")
    api = model_zoo.get_api(cfg, rc)
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, size=(2, 16), dtype=np.int32))
    lg_full, _ = transformer.forward(params, toks, cfg, rc)
    state = api.init_decode_state(2)
    step = jax.jit(api.decode_step)
    errs = []
    for i in range(16):
        lg, state = step(params, state, toks[:, i])
        errs.append(float(np.abs(np.asarray(lg) - np.asarray(lg_full[:, i])).max()))
    assert max(errs) < 2e-2, errs


def test_moe_decode_matches_with_no_drop_capacity():
    cfg = base.load_smoke("mixtral-8x7b")
    rc = base.RunConfig(seq_len=32, global_batch=2, kind="decode", remat=False,
                        q_block=16, kv_block=16, param_dtype="float32")
    api = model_zoo.get_api(cfg, rc)
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, size=(2, 12), dtype=np.int32))
    lg_full, _ = transformer.forward(params, toks, cfg, rc)
    state = api.init_decode_state(2)
    step = jax.jit(api.decode_step)
    for i in range(12):
        lg, state = step(params, state, toks[:, i])
        err = float(np.abs(np.asarray(lg) - np.asarray(lg_full[:, i])).max())
        assert err < 2e-4, (i, err)


def test_sliding_window_ring_cache_equals_full_cache():
    """SWA ring buffer (long_500k mechanism) == full cache with window mask."""
    cfg = dataclasses.replace(base.load_smoke("mixtral-8x7b"), sliding_window=8)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, size=(1, 24), dtype=np.int32))
    outs = {}
    for cache_len in (8, 32):   # ring (== window) vs oversized cache
        rc = base.RunConfig(seq_len=cache_len, global_batch=1, kind="decode",
                            remat=False, q_block=16, kv_block=16,
                            param_dtype="float32")
        api = model_zoo.get_api(cfg, rc)
        params = api.init(jax.random.PRNGKey(0))
        state = api.init_decode_state(1)
        step = jax.jit(api.decode_step)
        lgs = []
        for i in range(24):
            lg, state = step(params, state, toks[:, i])
            lgs.append(np.asarray(lg))
        outs[cache_len] = np.stack(lgs)
    assert np.allclose(outs[8], outs[32], atol=2e-4), \
        np.abs(outs[8] - outs[32]).max()


def test_vlm_prefix_changes_text_logits():
    cfg = base.load_smoke("internvl2-76b")
    rc = base.RunConfig(seq_len=24, global_batch=2, kind="train", remat=False,
                        q_block=16, kv_block=16)
    api = model_zoo.get_api(cfg, rc)
    params = api.init(jax.random.PRNGKey(0))
    b = _batch_for(cfg, rc)
    l1 = float(jax.jit(api.loss_fn)(params, b))
    b2 = dict(b, vis_embeds=b["vis_embeds"] + 1.0)
    l2 = float(jax.jit(api.loss_fn)(params, b2))
    assert l1 != l2


def test_param_counts_match_published_order():
    """Full configs: param_count within 15% of the published size."""
    expect = {
        "tinyllama-1.1b": 1.1e9, "yi-9b": 8.8e9, "granite-8b": 8.1e9,
        "mixtral-8x7b": 46.7e9, "mamba2-130m": 130e6,
        "qwen1.5-110b": 111e9, "grok-1-314b": 314e9,
        "internvl2-76b": 70e9,   # LLM backbone of the 76B (vision tower excl.)
        "whisper-tiny": 39e6, "hymba-1.5b": 1.52e9,
    }
    for arch, n in expect.items():
        got = base.load_arch(arch).param_count()
        assert abs(got - n) / n < 0.12, (arch, got, n)
    # MoE active counts (top-2 of 8)
    assert abs(base.load_arch("mixtral-8x7b").active_param_count() - 12.9e9) \
        / 12.9e9 < 0.05
