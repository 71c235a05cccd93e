"""Batched serving engine with packed (paper-layout) KV cache.

Prompts of different lengths decode in lockstep: each sequence tracks its own
position; while a sequence is still inside its prompt the engine feeds the
next prompt token (teacher forcing), afterwards it feeds the model's argmax.
The KV cache layout is controlled by ``RunConfig.kv_cache_bits``:
16 = bf16 (padded words, the paper's baseline), 8/4 = packed int blocks with
per-row scale markers (§2.4 packing + §4.2.2 metadata).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model_zoo
from repro.obs import instrument as obs


@dataclasses.dataclass
class ServeEngine:
    cfg: ModelConfig
    rc: RunConfig
    params: object = None
    seed: int = 0

    def __post_init__(self):
        self.api = model_zoo.get_api(self.cfg, self.rc)
        if self.params is None:
            self.params = self.api.init(jax.random.PRNGKey(self.seed))
        self._step = jax.jit(self.api.decode_step)
        self._kv_bytes: dict = {}

    def kv_cache_bytes(self, batch: int) -> int:
        # eval_shape retraces the decode state on every call; cache per
        # batch size so per-generate gauge updates stay off the trace path
        cached = self._kv_bytes.get(batch)
        if cached is None:
            state = jax.eval_shape(lambda: self.api.init_decode_state(batch))
            cached = sum(np.prod(s.shape) * s.dtype.itemsize
                         for s in jax.tree.leaves(state.caches))
            self._kv_bytes[batch] = cached
        return cached

    def generate(self, prompts: List[List[int]], max_new: int = 16,
                 greedy: bool = True) -> List[List[int]]:
        """Batched generation; returns generated token lists per prompt."""
        B = len(prompts)
        lens = np.array([len(p) for p in prompts])
        total = int(lens.max() + max_new)
        assert total <= self.rc.seq_len, (total, self.rc.seq_len)
        prompt_buf = np.zeros((B, int(lens.max())), np.int32)
        for i, p in enumerate(prompts):
            prompt_buf[i, :len(p)] = p

        if obs.enabled():
            obs.gauge_set("serve/kv_bytes", int(self.kv_cache_bytes(B)),
                          arch=self.cfg.name,
                          kv_bits=self.rc.kv_cache_bits)
        # spans/counters wrap the jitted decode step from outside; nothing
        # records inside the traced function (see repro.obs).  Per step:
        # dispatch, the host's wait for the step (fetch), the next tokens.
        with obs.span("serve/generate", arch=self.cfg.name, batch=B,
                      max_new=max_new):
            state = self.api.init_decode_state(B)
            out_tokens = [[] for _ in range(B)]
            cur = prompt_buf[:, 0].copy()
            for t in range(total - 1):
                with obs.span("serve/dispatch"):
                    logits, state = self._step(self.params, state,
                                               jnp.asarray(cur, jnp.int32))
                with obs.span("serve/fetch"):
                    nxt_model = np.asarray(jnp.argmax(logits, axis=-1))
                with obs.span("serve/select"):
                    nxt = np.zeros((B,), np.int32)
                    for i in range(B):
                        if t + 1 < lens[i]:
                            nxt[i] = prompt_buf[i, t + 1]   # still in prompt
                        else:
                            nxt[i] = nxt_model[i]
                            if len(out_tokens[i]) < max_new:
                                out_tokens[i].append(int(nxt_model[i]))
                    cur = nxt
        if obs.enabled():
            n_gen = sum(len(t) for t in out_tokens)
            obs.counter_inc("serve/generated_tokens", n_gen,
                            arch=self.cfg.name)
            obs.counter_inc("serve/decode_steps", total - 1,
                            arch=self.cfg.name)
        return out_tokens
