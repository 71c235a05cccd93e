"""Glue between a dense decoder's configuration file and the program.

Not a driver: the serve and train drivers share it.  It builds the
program's ``ModelConfig`` from the JSON configuration and wraps the
benchmark's weight dict (``weights.py``) in the program's parameter tree,
without copying an array.
"""
from __future__ import annotations

from repro.configs import base
from repro.models import layers, transformer


def model_config(c: dict) -> base.ModelConfig:
    if c.get("tie_word_embeddings") or c.get("attention_bias"):
        raise ValueError("the dense drivers take untied embeddings and no "
                         "attention bias")
    return base.ModelConfig(
        name=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c.get("head_dim") or 0,
        mlp_act="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=False)


def to_program(w: dict) -> transformer.DenseParams:
    return transformer.DenseParams(
        embed=layers.EmbedParams(table=w["embed"], unembed=w["unembed"],
                                 final_norm=w["final_norm"]),
        layers=transformer.LayerParams(
            ln1=w["ln1"],
            attn=layers.AttnParams(wq=w["wq"], wk=w["wk"], wv=w["wv"],
                                   wo=w["wo"], bq=None, bk=None, bv=None),
            ssm=None, ln_attn_out=None, ln_ssm_out=None, ln2=w["ln2"],
            mlp=layers.MlpParams(w_gate=w["w_gate"], w_up=w["w_up"],
                                 w_down=w["w_down"]),
            moe=None))


def from_program(p: transformer.DenseParams) -> dict:
    """The weight dict back from a parameter tree (e.g. a train state's)."""
    a, m = p.layers.attn, p.layers.mlp
    return {"embed": p.embed.table, "unembed": p.embed.unembed,
            "final_norm": p.embed.final_norm, "ln1": p.layers.ln1,
            "wq": a.wq, "wk": a.wk, "wv": a.wv, "wo": a.wo,
            "ln2": p.layers.ln2, "w_gate": m.w_gate, "w_up": m.w_up,
            "w_down": m.w_down}
