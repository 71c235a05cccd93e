"""Unified model API: config -> init / loss / prefill / decode / input specs.

``input_specs`` returns ShapeDtypeStructs (never allocates) for every model
input of a given model and run config.  Modality
frontends are stubs per the assignment: whisper receives precomputed frame
embeddings, internvl receives precomputed patch embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from . import encdec, transformer


class ModelApi(NamedTuple):
    init: Callable          # (key) -> params
    abstract_params: Callable  # () -> params ShapeDtypeStructs
    param_specs: Callable    # () -> logical-axis pytree
    loss_fn: Callable        # (params, batch) -> scalar loss
    prefill: Callable        # (params, batch) -> logits
    decode_step: Callable    # (params, state, tokens) -> (logits, state)
    init_decode_state: Callable  # (batch) -> state
    decode_state_specs: Callable  # () -> logical-axis pytree


def get_api(cfg: ModelConfig, rc: RunConfig) -> ModelApi:
    dtype = rc.jdtype
    if cfg.family == "encdec":
        return ModelApi(
            init=lambda key: encdec.init(key, cfg, dtype),
            abstract_params=lambda: jax.eval_shape(
                lambda: encdec.init(jax.random.PRNGKey(0), cfg, dtype)),
            param_specs=lambda: encdec.param_specs(cfg),
            loss_fn=lambda p, b: encdec.loss_fn(p, b, cfg, rc),
            prefill=lambda p, b: encdec.prefill(p, b, cfg, rc),
            decode_step=lambda p, s, t: encdec.decode_step(p, s, t, cfg, rc),
            init_decode_state=lambda batch: encdec.init_decode_state(
                cfg, rc, batch),
            decode_state_specs=lambda: encdec.decode_state_specs(cfg, rc),
        )
    return ModelApi(
        init=lambda key: transformer.init(key, cfg, dtype),
        abstract_params=lambda: jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), cfg, dtype)),
        param_specs=lambda: transformer.param_specs(cfg),
        loss_fn=lambda p, b: transformer.loss_fn(p, b, cfg, rc),
        prefill=lambda p, b: transformer.prefill(
            p, b["tokens"], cfg, rc, vis_embeds=b.get("vis_embeds")),
        decode_step=lambda p, s, t: transformer.decode_step(p, s, t, cfg, rc),
        init_decode_state=lambda batch: transformer.init_decode_state(
            cfg, rc, batch),
        decode_state_specs=lambda: transformer.decode_state_specs(cfg, rc),
    )


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, rc: RunConfig) -> Dict[str, Any]:
    """Abstract inputs for the step function.

    train / prefill: token batch (+ stub modality embeddings);
    decode: one token per sequence (the KV cache / state is part of the
    lowered function's carried inputs, built via init_decode_state under
    eval_shape).
    """
    B, S = rc.global_batch, rc.seq_len
    i32 = jnp.int32
    if cfg.family == "encdec":
        if rc.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((B,), i32)}
        return {
            "frames": jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                           rc.jdtype),
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
    if rc.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B,), i32)}
    out = {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
        "labels": jax.ShapeDtypeStruct((B, S), i32),
    }
    if cfg.family == "vlm":
        nv = cfg.n_vis_tokens
        out["tokens"] = jax.ShapeDtypeStruct((B, S - nv), i32)
        out["labels"] = jax.ShapeDtypeStruct((B, S - nv), i32)
        out["vis_embeds"] = jax.ShapeDtypeStruct((B, nv, cfg.d_model),
                                                 rc.jdtype)
    return out
