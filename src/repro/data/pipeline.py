"""Deterministic synthetic data pipeline (shard-aware, checkpointable).

Tokens are a stateless hash of (seed, step, position) so that any host can
regenerate any shard of any step — restart/elastic-re-mesh safe by
construction (the pipeline "state" is just the step counter, stored in the
checkpoint's extra dict).  The generated stream has local n-gram structure
(a small LCG-mixed Markov walk) so cross-entropy is learnable — integration
tests assert the loss drops.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model_zoo
from repro.obs import instrument as obs


def _hash2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    x = (a.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
         ^ b.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9))
    x ^= x >> np.uint64(31)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(29)
    return x


@dataclasses.dataclass
class SyntheticPipeline:
    cfg: ModelConfig
    rc: RunConfig
    seed: int = 0
    step: int = 0

    def state(self) -> Dict[str, int]:
        return {"data_step": self.step, "data_seed": self.seed}

    def restore(self, state: Dict[str, int]) -> None:
        self.step = int(state.get("data_step", 0))
        self.seed = int(state.get("data_seed", self.seed))

    def _tokens(self, step: int, batch: int, seq: int) -> np.ndarray:
        """Markov-ish walk: next token mixes previous token and position hash."""
        v = self.cfg.vocab
        rows = np.arange(batch, dtype=np.uint64)[:, None]
        cols = np.arange(seq + 1, dtype=np.uint64)[None, :]
        base = _hash2(rows + np.uint64(step * 131071 + self.seed),
                      cols)
        # local structure: token depends mostly on coarse position bucket
        walk = (base >> np.uint64(8)) % np.uint64(max(v // 16, 2))
        drift = (cols // np.uint64(17)) % np.uint64(max(v // 16, 2))
        toks = (walk + drift * np.uint64(16)) % np.uint64(v)
        return toks.astype(np.int32)

    def next(self) -> Dict[str, Any]:
        t0 = time.perf_counter()
        with obs.span("data/next"):
            batch = self._next()
        if not obs.enabled():
            return batch
        obs.hist_observe("data/batch_ms", (time.perf_counter() - t0) * 1e3,
                         arch=self.cfg.name)
        obs.counter_inc("data/batches", 1, arch=self.cfg.name)
        obs.counter_inc("data/bytes",
                        sum(np.asarray(v).nbytes for v in batch.values()),
                        arch=self.cfg.name)
        return batch

    def _next(self) -> Dict[str, Any]:
        cfg, rc = self.cfg, self.rc
        B, S = rc.global_batch, rc.seq_len
        if cfg.family == "vlm":
            S_text = S - cfg.n_vis_tokens
            toks = self._tokens(self.step, B, S_text)
            batch = {
                "tokens": toks[:, :-1],
                "labels": toks[:, 1:],
                "vis_embeds": self._embeds(B, cfg.n_vis_tokens),
            }
        elif cfg.family == "encdec":
            toks = self._tokens(self.step, B, S)
            batch = {
                "frames": self._embeds(B, cfg.enc_seq),
                "tokens": toks[:, :-1],
                "labels": toks[:, 1:],
            }
        else:
            toks = self._tokens(self.step, B, S)
            batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        self.step += 1
        return batch

    def _embeds(self, batch: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 7919 + self.step)
        x = rng.standard_normal((batch, n, self.cfg.d_model)) * 0.02
        return x.astype(np.float32)


def device_batch(batch: Dict[str, Any], cfg: ModelConfig, rc: RunConfig,
                 shardings: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Cast to the cell's input dtypes and place on device(s)."""
    specs = model_zoo.input_specs(cfg, rc)
    out = {}
    with obs.span("data/to_device"):
        for k, v in batch.items():
            spec = specs[k]
            arr = np.asarray(v)
            sh = shardings.get(k) if shardings else None
            out[k] = (jax.device_put(arr, sh) if sh is not None
                      else jax.device_put(arr))
            if out[k].dtype != spec.dtype:
                out[k] = out[k].astype(spec.dtype)
    return out
