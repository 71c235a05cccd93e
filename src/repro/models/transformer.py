"""Generic decoder-only LM frame: dense / vlm / moe / ssm / hybrid families.

One scan-over-stacked-layers body serves every decoder family (the HLO holds
a single layer regardless of depth — essential for the 80-layer dry-runs);
family-specific sublayers (attention, SSD mixer, MoE block) are selected
statically from the config, and unused param fields are None.

Whisper's encoder-decoder lives in models/encdec.py.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed import sharding as shd
from . import layers as L
from . import moe as M
from . import ssm as S


class LayerParams(NamedTuple):
    ln1: jax.Array
    attn: Optional[L.AttnParams]
    ssm: Optional[S.SsmParams]
    ln_attn_out: Optional[jax.Array]   # hymba per-branch norms
    ln_ssm_out: Optional[jax.Array]
    ln2: Optional[jax.Array]
    mlp: Optional[L.MlpParams]
    moe: Optional[M.MoeParams]


class DenseParams(NamedTuple):
    embed: L.EmbedParams
    layers: LayerParams      # stacked: leading dim n_layers


def _has_attn(cfg: ModelConfig) -> bool:
    return cfg.family in ("dense", "vlm", "moe", "hybrid", "encdec")


def _has_ssm(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid")


def _has_mlp(cfg: ModelConfig) -> bool:
    return cfg.family in ("dense", "vlm", "hybrid", "encdec")


def init_layer(key, cfg: ModelConfig, dtype) -> LayerParams:
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    return LayerParams(
        ln1=L.init_rmsnorm(d, dtype),
        attn=L.init_attn(k1, cfg, dtype) if _has_attn(cfg) else None,
        ssm=S.init_ssm(k3, cfg, dtype) if _has_ssm(cfg) else None,
        ln_attn_out=L.init_rmsnorm(d, dtype) if cfg.family == "hybrid" else None,
        ln_ssm_out=L.init_rmsnorm(d, dtype) if cfg.family == "hybrid" else None,
        ln2=L.init_rmsnorm(d, dtype) if (_has_mlp(cfg) or cfg.family == "moe")
        else None,
        mlp=L.init_mlp(k2, d, cfg.d_ff, cfg.mlp_act, dtype)
        if _has_mlp(cfg) else None,
        moe=M.init_moe(k2, cfg, dtype) if cfg.family == "moe" else None,
    )


def init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> DenseParams:
    ke, kl = jax.random.split(key)
    lkeys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg, dtype))(lkeys)
    return DenseParams(embed=L.init_embed(ke, cfg, dtype), layers=stacked)


def layer_specs(cfg: ModelConfig) -> LayerParams:
    return LayerParams(
        ln1=(None,),
        attn=L.attn_specs(cfg) if _has_attn(cfg) else None,
        ssm=S.ssm_specs() if _has_ssm(cfg) else None,
        ln_attn_out=(None,) if cfg.family == "hybrid" else None,
        ln_ssm_out=(None,) if cfg.family == "hybrid" else None,
        ln2=(None,) if (_has_mlp(cfg) or cfg.family == "moe") else None,
        mlp=L.mlp_specs(cfg.mlp_act) if _has_mlp(cfg) else None,
        moe=M.moe_specs() if cfg.family == "moe" else None,
    )


def param_specs(cfg: ModelConfig) -> DenseParams:
    stacked = jax.tree.map(lambda t: (None,) + t, layer_specs(cfg),
                           is_leaf=shd._is_logical_leaf)
    return DenseParams(embed=L.embed_specs(cfg), layers=stacked)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_fwd(cfg: ModelConfig, rc: RunConfig, x, pos, lp: LayerParams):
    """Returns (x, aux_loss_increment)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(x, lp.ln1, cfg.norm_eps)
    if cfg.family == "hybrid":
        a = L.attention(h, lp.attn, cfg, pos, rc.q_block, rc.kv_block)
        s = S.ssd_forward(lp.ssm, h, cfg)
        mix = 0.5 * (L.rmsnorm(a, lp.ln_attn_out, cfg.norm_eps)
                     + L.rmsnorm(s, lp.ln_ssm_out, cfg.norm_eps))
        x = x + mix
    elif cfg.family == "ssm":
        x = x + S.ssd_forward(lp.ssm, h, cfg)
    else:
        x = x + L.attention(h, lp.attn, cfg, pos, rc.q_block, rc.kv_block)
    if lp.ln2 is not None:
        h2 = L.rmsnorm(x, lp.ln2, cfg.norm_eps)
        if cfg.family == "moe":
            out, aux = M.moe_block(h2, lp.moe, cfg)
            x = x + out
        else:
            x = x + L.mlp(h2, lp.mlp, cfg.mlp_act)
    return shd.act(x, "batch", "seq", None), aux


def backbone(params: DenseParams, tokens: jax.Array, cfg: ModelConfig,
             rc: RunConfig, vis_embeds: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S_text) [+ optional stub prefix] -> (final hidden x, aux)."""
    x = L.embed(tokens, params.embed)
    if vis_embeds is not None:
        x = jnp.concatenate([vis_embeds.astype(x.dtype), x], axis=1)
    B, Sq, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(Sq)[None, :], (B, Sq))
    x = shd.act(x, "batch", "seq", None)

    body = functools.partial(_layer_fwd, cfg, rc)
    if rc.remat:
        if rc.remat_policy == "save_collectives":
            policy = jax.checkpoint_policies.save_only_these_names(
                "proj_out", "kv_gathered")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        body = jax.checkpoint(body, policy=policy)

    def scan_fn(carry, lp):
        x, aux = carry
        x, aux_inc = body(x, pos, lp)
        return (x, aux + aux_inc), None

    (x, aux), _ = jax.lax.scan(scan_fn, (x, jnp.zeros((), jnp.float32)),
                               params.layers)
    return x, aux / cfg.n_layers


def forward(params: DenseParams, tokens: jax.Array, cfg: ModelConfig,
            rc: RunConfig, vis_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Full logits (tests / tiny shapes; the loss path never calls this)."""
    x, aux = backbone(params, tokens, cfg, rc, vis_embeds)
    return L.logits(x, params.embed, cfg), aux


def loss_fn(params: DenseParams, batch, cfg: ModelConfig, rc: RunConfig):
    """batch: dict(tokens (B,S), labels (B,S) [, vis_embeds])."""
    vis = batch.get("vis_embeds")
    x, aux = backbone(params, batch["tokens"], cfg, rc, vis_embeds=vis)
    if vis is not None:
        x = x[:, vis.shape[1]:]              # loss over text positions only
    loss = L.fused_ce_loss(x, params.embed, cfg, batch["labels"],
                           batch.get("mask"))
    if cfg.family == "moe":
        loss = loss + 0.01 * aux
    return loss


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

class LayerCache(NamedTuple):
    kv: Optional[L.KVCache]
    ssm: Optional[S.SsmState]


class DecodeState(NamedTuple):
    caches: LayerCache       # stacked over layers
    pos: jax.Array           # (B,) next position per sequence


def init_decode_state(cfg: ModelConfig, rc: RunConfig, batch: int) -> DecodeState:
    s_cache = rc.seq_len
    if cfg.sliding_window:
        s_cache = min(s_cache, cfg.sliding_window)
    one = LayerCache(
        kv=jax.eval_shape(lambda: L.init_cache(
            cfg, batch, s_cache, rc.kv_cache_bits, rc.jdtype))
        if _has_attn(cfg) else None,
        ssm=jax.eval_shape(lambda: S.init_ssm_state(cfg, batch))
        if _has_ssm(cfg) else None,
    )
    cache = jax.tree.map(
        lambda s: jnp.zeros((cfg.n_layers,) + s.shape, s.dtype), one)
    return DecodeState(caches=cache, pos=jnp.zeros((batch,), jnp.int32))


def decode_state_specs(cfg: ModelConfig, rc: RunConfig) -> DecodeState:
    one = LayerCache(
        kv=L.cache_specs(rc.kv_cache_bits) if _has_attn(cfg) else None,
        ssm=S.ssm_state_specs() if _has_ssm(cfg) else None,
    )
    stacked = jax.tree.map(lambda t: (None,) + t, one,
                           is_leaf=shd._is_logical_leaf)
    return DecodeState(caches=stacked, pos=(None,))


def decode_step(params: DenseParams, state: DecodeState, tokens: jax.Array,
                cfg: ModelConfig, rc: RunConfig) -> Tuple[jax.Array, DecodeState]:
    """One decode step.  tokens: (B,) -> (logits (B, V), new state)."""
    x = L.embed(tokens[:, None], params.embed)            # (B, 1, d)
    layers, moe = params.layers, params.layers.moe
    if moe is not None:
        # every layer's experts as one table of groups, handed whole to
        # each layer's grouped matmuls; the layer loop carries the routers
        count = cfg.held_experts[1]
        table = [w.reshape((-1,) + w.shape[2:])
                 for w in (moe.w_gate, moe.w_up, moe.w_down)]
        layers = layers._replace(moe=moe._replace(w_gate=None, w_up=None,
                                                  w_down=None))

    def scan_fn(x, layer):
        lp, cache, i = layer
        h = L.rmsnorm(x, lp.ln1, cfg.norm_eps)
        new_kv, new_ssm = cache.kv, cache.ssm
        if cfg.family == "hybrid":
            a, new_kv = L.decode_attention(h, lp.attn, cfg, cache.kv,
                                           state.pos, rc.kv_cache_bits,
                                           cfg.sliding_window)
            s, new_ssm = S.ssd_decode(lp.ssm, h, cache.ssm, cfg)
            x = x + 0.5 * (L.rmsnorm(a, lp.ln_attn_out, cfg.norm_eps)
                           + L.rmsnorm(s, lp.ln_ssm_out, cfg.norm_eps))
        elif cfg.family == "ssm":
            s, new_ssm = S.ssd_decode(lp.ssm, h, cache.ssm, cfg)
            x = x + s
        else:
            a, new_kv = L.decode_attention(h, lp.attn, cfg, cache.kv,
                                           state.pos, rc.kv_cache_bits,
                                           cfg.sliding_window)
            x = x + a
        if lp.ln2 is not None:
            h2 = L.rmsnorm(x, lp.ln2, cfg.norm_eps)
            if cfg.family == "moe":
                out, _ = M.moe_block(h2, lp.moe._replace(
                    w_gate=table[0], w_up=table[1], w_down=table[2]),
                    cfg, group0=i * count)
                x = x + out
            else:
                x = x + L.mlp(h2, lp.mlp, cfg.mlp_act)
        return x, LayerCache(kv=new_kv, ssm=new_ssm)

    index = None if moe is None else jnp.arange(cfg.n_layers)
    x, caches = jax.lax.scan(scan_fn, x, (layers, state.caches, index))
    lg = L.logits(x, params.embed, cfg)[:, 0]
    return lg, DecodeState(caches=caches, pos=state.pos + 1)


def prefill(params: DenseParams, tokens: jax.Array, cfg: ModelConfig,
            rc: RunConfig, vis_embeds: Optional[jax.Array] = None) -> jax.Array:
    """Prefill: logits for the LAST position only (serving semantics —
    materializing (B, S, 150k-vocab) logits would dwarf the model)."""
    x, _ = backbone(params, tokens, cfg, rc, vis_embeds=vis_embeds)
    return L.logits(x[:, -1:], params.embed, cfg)[:, 0]
