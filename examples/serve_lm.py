"""Batched serving with a packed (paper-layout) KV cache.

Generates continuations for a batch of mixed-length prompts twice — bf16
cache vs packed int8 — and reports cache footprint + agreement.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.serve.engine import ServeEngine
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

cfg = ModelConfig(name="serve-demo", family="dense", n_layers=4, d_model=256,
                  n_heads=8, n_kv_heads=4, d_ff=768, vocab=4096)

prompts = [[1, 7, 42], [9, 9], [100, 200, 300, 400], [5]]

engines = {}
for bits in (16, 8):
    rc = RunConfig(seq_len=64, global_batch=len(prompts), kind="decode",
                   remat=False, kv_cache_bits=bits)
    eng = ServeEngine(cfg, rc, params=engines.get(16, None) and engines[16].params,
                      seed=0)
    engines[bits] = eng
    out = eng.generate(prompts, max_new=12)
    print(f"kv_cache_bits={bits}: cache={eng.kv_cache_bytes(len(prompts)):,} B")
    for p, o in zip(prompts, out):
        print(f"  prompt {p} -> {o}")

agree = np.mean([
    a == b for a, b in zip(
        sum(engines[16].generate(prompts, max_new=12), []),
        sum(engines[8].generate(prompts, max_new=12), []))])
print(f"\nint8-packed vs bf16 greedy agreement: {agree:.0%} "
      "(quantization may flip rare near-ties)")
