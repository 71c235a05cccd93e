"""Serve driver for a Mixtral-style MoE decoder: ``drivers/serve.py``'s
closed-loop batch generation through ``ServeEngine.generate``, with the
program told which experts this chip holds (``ModelConfig.expert_share``,
from the configuration's ``expert_share``).

The window, the traced steps and the sample of requests checked are the
serve driver's.  Correctness: the sampled requests go through the plain
f32 reference (``reference/mixtral.py``) over their prompt and served
tokens, a few requests per call.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best at
that position, over the positions where the reference's routing is clear:
a position is left out where, at some layer, the reference's k-th and
(k+1)-th router logits lie within ``router_margin`` of each other, for
there a rounding of the router's bf16 input may pick the other expert, a
different but sound result.  Those positions are judged by the reference
alone, counted, and bounded by a limit of their own.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import counts
import counts_moe
import weights_moe
from drivers import serve
from reference import mixtral as ref
from repro.configs import base
from repro.models import layers, moe, transformer
from repro.serve.engine import ServeEngine

#: requests per call of the reference: a fixed shape, compiled once
REF_REQUESTS = 4


def model_config(c: dict) -> base.ModelConfig:
    if (c.get("tie_word_embeddings") or c.get("attention_bias")
            or c.get("sliding_window")):
        raise ValueError("the MoE driver takes untied embeddings, no "
                         "attention bias and dense attention")
    return base.ModelConfig(
        name=c["name"], family="moe",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c.get("head_dim") or 0,
        mlp_act="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=False,
        n_experts=c["num_local_experts"], topk=c["num_experts_per_tok"],
        expert_share=weights_moe.share(c))


def to_program(w: dict) -> transformer.DenseParams:
    return transformer.DenseParams(
        embed=layers.EmbedParams(table=w["embed"], unembed=w["unembed"],
                                 final_norm=w["final_norm"]),
        layers=transformer.LayerParams(
            ln1=w["ln1"],
            attn=layers.AttnParams(wq=w["wq"], wk=w["wk"], wv=w["wv"],
                                   wo=w["wo"], bq=None, bk=None, bv=None),
            ssm=None, ln_attn_out=None, ln_ssm_out=None, ln2=w["ln2"],
            mlp=None,
            moe=moe.MoeParams(router=w["router"], w_gate=w["w_gate"],
                              w_up=w["w_up"], w_down=w["w_down"])))


class Driver(serve.Driver):
    def setup(self) -> None:
        t = self.t
        self.cfg = model_config(self.c)
        self.rc = base.RunConfig(seq_len=int(t["cache_len"]),
                                 global_batch=int(t["requests_per_call"]),
                                 kind="decode", kv_cache_bits=self.kv_bits)
        self.w = weights_moe.make(self.seed, self.c, jnp.bfloat16)
        self.engine = ServeEngine(self.cfg, self.rc,
                                  params=to_program(self.w))
        self.rng = np.random.default_rng(self.seed)
        B = self.rc.global_batch
        self.lengths = np.linspace(int(t["prompt_min"]), int(t["prompt_max"]),
                                   B).round().astype(int)
        # one lockstep step at the window's batch and cache: compiles the
        # decode step, the cache's zeros and the host-side argmax
        self.engine.generate([[1]] * B, max_new=1)

    def layer_counts(self, pk) -> dict:
        """Decode steps in the traced window, their least time (step t
        attends to t + 1 cached positions; ``counts_moe.decode_step``), and
        the least time of their grouped expert matmuls
        (``counts_moe.expert_step``)."""
        B, steps = self.rc.global_batch, len(self.traced)
        least = sum(counts.least_seconds(
            counts_moe.decode_step(self.c, B, t + 1, self.kv_bits)[0],
            counts_moe.decode_step(self.c, B, t, self.kv_bits)[1], pk)
            for t in self.traced)
        flops, nbytes = counts_moe.expert_step(self.c, B)
        return {"decode_steps": steps, "least_s": least,
                "expert_least_s": steps * counts.least_seconds(flops, nbytes,
                                                               pk)}

    def reference_gaps(self):
        """(gaps, router margins), each (requests, new tokens): the
        reference's ``served_gaps`` over the sampled requests, in the
        sample's order (``serve.Driver.sample``)."""
        pairs = self.sample()
        reqs = [(self.calls[c][0][i], self.calls[c][1][i]) for c, i in pairs]
        reqs = [(p, o) for p, o in reqs if self._well_formed(o)]
        n_new, S = int(self.t["new_tokens"]), int(self.t["cache_len"])
        R = -(-len(reqs) // REF_REQUESTS) * REF_REQUESTS
        toks = np.zeros((R, S), np.int32)
        starts = np.zeros((R,), np.int32)
        served = np.zeros((R, n_new), np.int32)
        for r, (p, o) in enumerate(reqs):
            seq = list(p) + list(o[:-1])
            toks[r, :len(seq)] = seq
            starts[r], served[r] = len(p) - 1, o
        first, _ = weights_moe.share(self.c)
        out = [ref.served_gaps(self.w, jnp.asarray(toks[r:r + REF_REQUESTS]),
                               jnp.asarray(starts[r:r + REF_REQUESTS]),
                               jnp.asarray(served[r:r + REF_REQUESTS]),
                               self.c, first)
               for r in range(0, R, REF_REQUESTS)]
        return tuple(np.concatenate([np.asarray(o[i]) for o in out])[:len(reqs)]
                     for i in (0, 1))

    def check(self):
        self.engine = None                  # the program's state goes
        self.gaps, self.margins = self.reference_gaps()
        tie = self.margins < float(self.t["router_margin"])
        clear = self.gaps[~tie]
        lim = self.t["limits"]
        return [("max_logit_gap", float(clear.max()) if clear.size else 0.0,
                 lim["max_logit_gap"]),
                ("near_tie_positions", int(tie.sum()),
                 lim["near_tie_positions"]),
                ("malformed_requests", self.failed, 0),
                ("compared_tokens", int(clear.size),
                 int(self.t["check_requests"]) * int(self.t["new_tokens"])
                 - lim["near_tie_positions"], "min")]
