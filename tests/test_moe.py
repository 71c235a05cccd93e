"""The dropless expert layer (``models/moe.py``) against the benchmark's
plain f32 reference (``benchmarks/tpu/reference/mixtral.py``), on seeded
random weights at a small size."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import moe
from repro.serve.engine import ServeEngine

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmarks", "tpu"))
import weights_moe  # noqa: E402
from drivers import serve_moe  # noqa: E402
from reference import mixtral as ref  # noqa: E402

F32 = jnp.float32
CFG = base.ModelConfig(name="moe-test", family="moe", n_layers=1,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
                       vocab=128, n_experts=8, topk=2)
TOKENS = (4, 16)                                # (B, S): 64 tokens


def _params(cfg=CFG, seed=0):
    return moe.init_moe(jax.random.PRNGKey(seed), cfg, F32)


def _share(p, first, count):
    return p._replace(w_gate=p.w_gate[first:first + count],
                      w_up=p.w_up[first:first + count],
                      w_down=p.w_down[first:first + count])


def _reference(x, p, first=0):
    """The reference's FFN of one layer on x, the share given by ``p``'s
    experts from ``first``."""
    w = {"router": p.router[None], "w_gate": p.w_gate[None],
         "w_up": p.w_up[None], "w_down": p.w_down[None]}
    out, _ = ref.ffn(x.astype(F32), w, 0, {"num_experts_per_tok": CFG.topk},
                     first)
    return out


def _inputs(skewed: bool, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), TOKENS + (CFG.d_model,))
    p = _params()
    if skewed:
        # every token's top expert is expert 0
        x = x + 2.0
        p = p._replace(router=p.router.at[:, 0].set(0.5))
    return x, p


@pytest.mark.parametrize("skewed", [False, True])
def test_dropless_layer_equals_the_reference(skewed):
    x, p = _inputs(skewed)
    out, aux = jax.jit(lambda x, p: moe.moe_block(x, p, CFG))(x, p)
    want = _reference(x, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))
    if skewed:
        top = np.asarray(jnp.argmax(x.reshape(-1, CFG.d_model) @ p.router,
                                    axis=-1))
        assert (top == 0).all()
        # a capacity of 1.25 x the even share would have dropped most of
        # expert 0's rows
        assert (top == 0).sum() > int(1.25 * CFG.topk * top.size
                                      / CFG.n_experts)


@pytest.mark.parametrize("first,count", [(0, 4), (4, 4), (2, 3)])
def test_a_share_equals_the_reference_share(first, count):
    x, p = _inputs(skewed=False)
    cfg = dataclasses.replace(CFG, expert_share=(first, count))
    part = _share(p, first, count)
    out, _ = moe.moe_block(x, part, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference(x, part, first)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("skewed", [False, True])
def test_shares_add_up_to_the_whole_layer(skewed):
    x, p = _inputs(skewed)
    whole, _ = moe.moe_block(x, p, CFG)
    parts = []
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, expert_share=(first, 4))
        parts.append(moe.moe_block(x, _share(p, first, 4), cfg)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(_reference(x, p)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("share", [None, (4, 4)])
def test_stacked_layers_equal_each_layer(share):
    """The decode step's form, every layer's experts in one table of groups
    and the layer's groups picked by ``group0``, computes what that layer's
    own parameters give."""
    cfg = dataclasses.replace(CFG, expert_share=share)
    first, count = cfg.held_experts
    layers = [_share(_params(seed=s), first, count) for s in range(3)]
    table = [jnp.concatenate([getattr(p, k) for p in layers])
             for k in ("w_gate", "w_up", "w_down")]
    step = jax.jit(lambda x, p, i: moe.moe_block(x, p, cfg, group0=i)[0])
    x, _ = _inputs(skewed=False)
    for i, p in enumerate(layers):
        whole = p._replace(w_gate=table[0], w_up=table[1], w_down=table[2])
        np.testing.assert_allclose(np.asarray(step(x, whole, i * count)),
                                   np.asarray(moe.moe_block(x, p, cfg)[0]),
                                   rtol=1e-5, atol=1e-6)


def test_init_holds_the_share_only():
    cfg = dataclasses.replace(CFG, expert_share=(2, 3))
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, F32)
    assert p.router.shape == (CFG.d_model, CFG.n_experts)
    assert p.w_gate.shape == (3, CFG.d_model, CFG.d_ff)
    assert p.w_down.shape == (3, CFG.d_ff, CFG.d_model)
    assert CFG.held_experts == (0, CFG.n_experts)


# the program's Mixtral smoke widths as a configuration file of the benchmark
SMOKE = base.load_smoke("mixtral-8x7b")
SMOKE_JSON = {"name": "mixtral-smoke", "hidden_size": SMOKE.d_model,
              "intermediate_size": SMOKE.d_ff,
              "num_attention_heads": SMOKE.n_heads,
              "num_key_value_heads": SMOKE.n_kv_heads,
              "num_hidden_layers": SMOKE.n_layers,
              "vocab_size": SMOKE.vocab, "rms_norm_eps": SMOKE.norm_eps,
              "rope_theta": SMOKE.rope_theta,
              "num_local_experts": SMOKE.n_experts,
              "num_experts_per_tok": SMOKE.topk, "sliding_window": None,
              "tie_word_embeddings": False}


def test_smoke_config_is_the_program_config():
    assert serve_moe.model_config(SMOKE_JSON) == dataclasses.replace(
        SMOKE, expert_share=(0, SMOKE.n_experts))
    assert json.loads(json.dumps(SMOKE_JSON)) == SMOKE_JSON


@pytest.mark.parametrize("seed", [3, 4])
def test_generate_matches_the_reference_forward(seed):
    """``ServeEngine.generate`` through the decode step and an unpacked
    cache serves, at every position, the reference's own best token: the
    gap of the served token's reference logit below the best is f32
    rounding alone."""
    c = SMOKE_JSON
    rc = base.RunConfig(seq_len=40, global_batch=3, kind="decode",
                        kv_cache_bits=16, param_dtype="float32")
    w = weights_moe.make(seed, c, F32)
    engine = ServeEngine(serve_moe.model_config(c), rc,
                         params=serve_moe.to_program(w))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, c["vocab_size"], n).tolist() for n in (5, 9, 17)]
    new = 12
    out = engine.generate(prompts, max_new=new)
    S = max(len(p) for p in prompts) + new
    toks = np.zeros((3, S), np.int32)
    for r, (p, o) in enumerate(zip(prompts, out)):
        toks[r, :len(p) + new - 1] = p + o[:-1]
    starts = np.array([len(p) - 1 for p in prompts], np.int32)
    gaps, margins = ref.served_gaps(w, jnp.asarray(toks), jnp.asarray(starts),
                                    jnp.asarray(np.array(out, np.int32)), c)
    assert np.asarray(gaps).max() < 1e-4, np.asarray(gaps).max()
    assert np.asarray(margins).min() >= 0
