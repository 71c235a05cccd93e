"""Serve driver: closed-loop batch generation through ``ServeEngine.generate``.

One unit is one ``generate`` call of ``requests_per_call`` requests.  The
prompt lengths are the same fixed set in every call and every seed (evenly
spaced from ``prompt_min`` to ``prompt_max``), shuffled by the seed; the
prompt tokens are drawn from the seed.  So every call does the same work.

Correctness: once the window has closed, a sample of the requests it
finished, drawn from the seed and holding a request with the longest
prompt, goes through the plain f32 reference (``reference/granite.py``)
over its prompt and served tokens.  The number compared is the widest gap
by which a served token's reference logit lies below the reference's best
at that position (greedy decoding serves the program's own best).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import counts
import weights
from reference import granite as ref
from drivers import dense
from repro.configs import base
from repro.serve.engine import ServeEngine


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 kv_cache_bits: int = 0):
        self.c, self.t, self.seed = config, traffic, seed
        self.kv_bits = kv_cache_bits or int(traffic["kv_cache_bits"])
        self.calls = []                 # (prompts, outputs) of each call
        self.traced = range(0)          # the decode steps traced
        self.attempted = self.failed = 0

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        t = self.t
        self.cfg = dense.model_config(self.c)
        self.rc = base.RunConfig(seq_len=int(t["cache_len"]),
                                 global_batch=int(t["requests_per_call"]),
                                 kind="decode", kv_cache_bits=self.kv_bits)
        self.w = weights.make(self.seed, self.c, jnp.bfloat16)
        self.engine = ServeEngine(self.cfg, self.rc,
                                  params=dense.to_program(self.w))
        self.rng = np.random.default_rng(self.seed)
        B = self.rc.global_batch
        self.lengths = np.linspace(int(t["prompt_min"]), int(t["prompt_max"]),
                                   B).round().astype(int)
        # one lockstep step at the window's batch and cache: compiles the
        # decode step, the cache's zeros and the host-side argmax
        self.engine.generate([[1]] * B, max_new=1)

    # -- the window -------------------------------------------------------
    def unit(self) -> None:
        B, V = self.rc.global_batch, self.cfg.vocab
        lens = self.rng.permutation(self.lengths)
        prompts = [self.rng.integers(0, V, n).tolist() for n in lens]
        with jax.profiler.TraceAnnotation("bench/generate"):
            out = self.engine.generate(prompts, max_new=int(self.t["new_tokens"]))
        self.calls.append((prompts, out))
        self.attempted += B
        self.failed += sum(not self._well_formed(o) for o in out)

    def _well_formed(self, out) -> bool:
        return (len(out) == int(self.t["new_tokens"])
                and all(0 <= x < self.cfg.vocab for x in out))

    def traced_unit(self, start, stop) -> float:
        """One call of the window, of which the last ``trace_steps`` decode
        steps run under the profiler (``start`` .. ``stop``).  A whole call
        holds some hundreds of steps of tens of thousands of device ops
        each, past the 2 GB that the profiler keeps; it drops the rest.
        Returns the traced steps' window, from the first's dispatch to the
        last's end."""
        engine, step = self.engine, self.engine._step
        n = int(self.t["trace_steps"])
        first = self.steps_per_call() - n
        seen, span, window = [0], [0.0, 0.0], []

        def traced(*args):
            i = seen[0]
            seen[0] += 1
            if i == first:
                start()
                window.append(jax.profiler.TraceAnnotation("bench/window"))
                window[0].__enter__()
                span[0] = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/step"):
                out = step(*args)
            if i == first + n - 1:
                jax.block_until_ready(out)
                span[1] = time.perf_counter()
                window[0].__exit__(None, None, None)
                stop()
            return out

        engine._step = traced
        try:
            self.unit()
        finally:
            engine._step = step
        self.traced = range(first, first + n)
        return span[1] - span[0]

    def e2e(self, window_s: float) -> dict:
        served = sum(len(o) for _, outs in self.calls for o in outs)
        return {"serve_tokens_per_s": served / window_s}

    # -- per-layer counts ---------------------------------------------------
    def steps_per_call(self) -> int:
        return int(self.lengths.max()) + int(self.t["new_tokens"]) - 1

    def layer_counts(self, pk) -> dict:
        """Decode steps in the traced window and their least time; step t
        attends to t + 1 cached positions."""
        B = self.rc.global_batch
        least = sum(counts.least_seconds(
            counts.decode_step_flops(self.c, B, t + 1),
            counts.decode_step_bytes(self.c, B, t, self.kv_bits), pk)
            for t in self.traced)
        return {"decode_steps": len(self.traced), "least_s": least}

    def hlo_text(self) -> str:
        B = self.rc.global_batch
        state = jax.eval_shape(lambda: self.engine.api.init_decode_state(B))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        return self.engine._step.lower(self.engine.params, state, tok) \
            .compile().as_text()

    # -- correctness -----------------------------------------------------------
    def sample(self):
        """(call, request) pairs to compare, drawn from the seed: a request
        with the longest prompt first, then others."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, self.seed >> 32, 1])
        c0 = int(rng.integers(len(self.calls)))
        longest = (c0, int(np.argmax([len(p) for p in self.calls[c0][0]])))
        everyone = [(c, i) for c, (prompts, _) in enumerate(self.calls)
                    for i in range(len(prompts))]
        rest = [everyone[j] for j in rng.permutation(len(everyone))
                if everyone[j] != longest]
        return [longest] + rest[:int(self.t["check_requests"]) - 1]

    def check(self):
        self.engine = None                  # the program's state goes
        pairs = self.sample()
        reqs = [(self.calls[c][0][i], self.calls[c][1][i]) for c, i in pairs]
        reqs = [(p, o) for p, o in reqs if self._well_formed(o)]
        n_new = int(self.t["new_tokens"])
        S = max(len(p) for p, _ in reqs) + n_new - 1
        toks = np.zeros((len(reqs), S), np.int32)
        for r, (p, o) in enumerate(reqs):
            seq = list(p) + list(o[:-1])
            toks[r, :len(seq)] = seq
        starts = np.array([len(p) - 1 for p, _ in reqs], np.int32)
        served = np.array([o for _, o in reqs], np.int32)
        gaps = np.asarray(ref.served_gaps(self.w, jnp.asarray(toks),
                                          jnp.asarray(starts),
                                          jnp.asarray(served), self.c))
        limits = self.t["limits"]
        return [("max_logit_gap", float(gaps.max()), limits["max_logit_gap"]),
                ("malformed_requests", self.failed, 0),
                ("compared_tokens", int(gaps.size),
                 int(self.t["check_requests"]) * n_new, "min")]
