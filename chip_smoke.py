#!/usr/bin/env python3
"""Smoke test of the main paths on TPU chips, through the user entry points.

    python chip_smoke.py             # one chip: serve, train, stencil/codec
    python chip_smoke.py --chips 4   # four chips: sharded train step only

One chip runs three phases in this one process:

* serve: granite-8b at its published widths, depth cut to 8 of 36 layers,
  random weights from ``SEED``.  ``ServeEngine.generate`` for 8 requests
  with a bf16 and an int8 KV cache; decode through the cache is checked
  against ``prefill`` (the flash kernel) on one prompt.
* train: ``train.loop.train`` at the same widths, 3 layers, batch 2 at
  4096 tokens (what the compiled step's memory analysis fits in 16 GB),
  flash forward and backward; first the kernel's bf16 gradients are
  checked against f32 attention at granite's head layout.
* stencil/codec: the paper's kernels (jacobi, bitplane pack/unpack, KV
  quantization) against their jnp references in ``kernels/ref.py``.

``--chips 4`` runs the training step on a (data 2, model 2) mesh against
the same step on one chip, and the plain against the 8-bit compressed
gradient exchange on a (pod 2, data 2) mesh.

Each check prints a line.  The last line is ``{"ok": true, "device":
{...}}``; without a TPU, or when any check fails, the script exits nonzero
and does not print it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import base  # noqa: E402
from repro.data.pipeline import SyntheticPipeline, device_batch  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model_zoo  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.train import step as ts  # noqa: E402
from repro.train.loop import LoopConfig, train  # noqa: E402

# Decode (one token at a time through the bf16 cache, bf16 k/v einsums)
# and prefill (flash kernel over the whole prompt) differ only in bf16
# rounding.  On 8 layers at d_model 1024 (CPU, flash in interpret mode, a
# 384-token prompt) their relative RMS logit gap was 1.3e-2; a decode
# without RoPE was off by 0.76, and one whose positions started 7 slots in,
# so that it attended to 7 unwritten cache slots, by 0.24.  5e-2 passes the
# first with 4x margin and fails both faults by 5x or more.
DECODE_PREFILL_RTOL = 5e-2
# An int8 cache rounds every k/v row to 1/127 of its largest entry; in the
# same run the logits moved by 1.8e-2 relative RMS.  0.1 is still below
# both faults above.
INT8_CACHE_RTOL = 0.1
# Flash dq/dk/dv with bf16 inputs against f32 attention.  In interpret mode
# on CPU (S 256-512, granite's head_dim) the relative RMS read 1.6e-3 to
# 2.4e-3; a backward without the delta term read 0.35 to 0.63 on dq and dk,
# one that summed dk/dv over the last query group only, 0.50 to 0.81.
FLASH_GRAD_RTOL = 3e-2
# what tests/test_distributed.py allows: sharded vs one device
DIST_LOSS_ATOL = 5e-3
# 8-bit vs plain gradient exchange, on every step.  On four v5e chips the
# two differed by at most 9.6e-5 over 3 steps.  The losses fall mostly
# because each step sees a new batch: a run with lr 0, which applies no
# update at all, differed from the plain one by 6.7e-5 at step 2 and 9.4e-4
# at step 3 (one v5e chip).  3e-4 lies 3x from either reading.
COMPRESSED_LOSS_ATOL = 3e-4
# every tolerance above was set from runs at this seed
SEED = 0


class CheckFailed(Exception):
    pass


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"[{'PASS' if ok else 'FAIL'}] {name}" + (f": {detail}" if detail else ""),
          flush=True)
    if not ok:
        raise CheckFailed(name)


def rel_rms(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


class CompileLog:
    """Seconds spent compiling (or reading the persistent cache) per phase."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses

    def since(self, snap) -> str:
        s, h, m = snap
        return (f"compile {self.seconds - s:.1f}s, persistent cache "
                f"{self.hits - h} hits / {self.misses - m} misses")


def granite(n_layers: int) -> base.ModelConfig:
    return dataclasses.replace(base.load_arch("granite-8b"), n_layers=n_layers)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def decode_through(engine: ServeEngine, tokens) -> np.ndarray:
    """Logits at the last token, fed one at a time through the KV cache."""
    step = jax.jit(engine.api.decode_step)
    state = engine.api.init_decode_state(1)
    logits = None
    for t in tokens:
        logits, state = step(engine.params, state, jnp.asarray([t], jnp.int32))
    return np.asarray(logits[0], np.float32)


def serve_phase(cfg: base.ModelConfig, *, seq_len: int,
                prompt_lens, max_new: int) -> None:
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in prompt_lens]
    engines, outs = {}, {}
    for bits in (16, 8):
        rc = base.RunConfig(seq_len=seq_len, global_batch=len(prompts),
                            kind="decode", kv_cache_bits=bits)
        params = engines[16].params if engines else None
        eng = ServeEngine(cfg, rc, params=params, seed=SEED)
        t0 = time.perf_counter()
        out = eng.generate(prompts, max_new=max_new)
        dt = time.perf_counter() - t0
        engines[bits], outs[bits] = eng, out
        ok = (len(out) == len(prompts)
              and all(len(o) == max_new for o in out)
              and all(0 <= t < cfg.vocab for o in out for t in o))
        check(f"serve generate kv_cache_bits={bits}", ok,
              f"{len(prompts)} requests, prompts {min(prompt_lens)}-"
              f"{max(prompt_lens)} tokens, {max_new} new each, "
              f"{dt:.2f}s wall (first call compiles)")
    agree = np.mean([a == b for a, b in zip(sum(outs[16], []),
                                            sum(outs[8], []))])
    print(f"int8 vs bf16 cache: {agree:.1%} of greedy tokens agree", flush=True)

    prompt = prompts[-1]
    eng16 = engines[16]
    pre = np.asarray(jax.jit(eng16.api.prefill)(
        eng16.params, {"tokens": jnp.asarray([prompt], jnp.int32)})[0],
        np.float32)
    dec16 = decode_through(eng16, prompt)
    dec8 = decode_through(engines[8], prompt)
    check("logits finite", all(np.isfinite(x).all() for x in (pre, dec16, dec8)))
    err = rel_rms(dec16, pre)
    check("decode (bf16 cache) matches prefill (flash)",
          err < DECODE_PREFILL_RTOL,
          f"relative RMS {err:.2e} < {DECODE_PREFILL_RTOL:g} at position "
          f"{len(prompt) - 1}; argmax {int(dec16.argmax())} vs "
          f"{int(pre.argmax())}")
    err8 = rel_rms(dec8, dec16)
    check("int8-cache logits track bf16-cache logits", err8 < INT8_CACHE_RTOL,
          f"relative RMS {err8:.2e} < {INT8_CACHE_RTOL:g}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def flash_grad_errors(shape, bq: int, bk: int, interpret: bool = False):
    """Relative RMS of the flash kernel's dq, dk, dv against the reference.

    ``shape`` is (B, S, KV, G, D).  The kernel takes bf16 inputs, as in the
    train step; the reference is ``blockwise_attention`` in f32 at full
    matmul precision on the same rounded inputs.
    """
    from repro.kernels.flash_attention import flash_attention
    from repro.models.layers import blockwise_attention
    B, S, KV, G, D = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.bfloat16)

    def loss(o):
        o = o.astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))

    def kernel_loss(q, k, v):
        return loss(flash_attention(q, k, v, True, 0, bq, bk, interpret))

    def ref_loss(q, k, v):
        return loss(blockwise_attention(q.reshape(B, S, KV * G, D), k, v,
                                        causal=True, window=0, q_block=bq,
                                        kv_block=bk))

    grads = jax.jit(jax.grad(kernel_loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        refs = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    return {name: rel_rms(a.astype(jnp.float32), b)
            for name, a, b in zip(("dq", "dk", "dv"), grads, refs)}


def train_phase(cfg: base.ModelConfig, rc: base.RunConfig, steps: int,
                out_dir: str) -> None:
    shape = (1, 2048, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd)
    errs = flash_grad_errors(shape, bq=512, bk=1024)
    check("flash gradients (bf16) match f32 attention",
          max(errs.values()) < FLASH_GRAD_RTOL,
          f"(B, S, KV, G, D) {shape}: relative RMS " +
          ", ".join(f"{n} {e:.2e}" for n, e in errs.items()) +
          f" < {FLASH_GRAD_RTOL:g}")

    os.makedirs(out_dir, exist_ok=True)
    ckpt = tempfile.mkdtemp(prefix="train_ckpt_", dir=out_dir)
    try:
        hist = train(cfg, rc, LoopConfig(total_steps=steps, ckpt_every=steps,
                                         ckpt_dir=ckpt), log_every=0)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    losses = hist["loss"]
    print("train losses:", losses, "step seconds:",
          [round(t, 3) for t in hist["step_time"]], flush=True)
    check("train restarts", hist["restarts"] == 0, f"{hist['restarts']}")
    check("train losses finite",
          len(losses) == steps and bool(np.isfinite(losses).all()),
          f"{len(losses)} steps of {rc.global_batch}x{rc.seq_len} tokens")


# ---------------------------------------------------------------------------
# stencil and codec (the paper's path)
# ---------------------------------------------------------------------------

def same(a, b) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype and \
        bool(jnp.array_equal(a, b))


def stencil_codec_phase(n: int, rows: int) -> None:
    key = jax.random.PRNGKey(SEED)
    k1, k2, k3 = jax.random.split(key, 3)

    t_steps, width = 8, 512
    x = jax.random.normal(k1, (n,), jnp.float32)
    y = ops.jacobi1d_tiled(x, t_steps, width=width, use_pallas="pallas")
    y_ref = jax.jit(ref.jacobi_chunked_ref, static_argnums=1)(x, t_steps)
    err = float(jnp.abs(y - y_ref).max())
    check("jacobi1d_tiled vs jacobi_chunked_ref", err < 1e-5,
          f"{n} f32 cells, T={t_steps}, W={width}: max |err| {err:.2e}")

    block, bits = 1024, 8
    d = jax.random.randint(k2, (rows, block), -60, 61, jnp.int32)
    q = jnp.cumsum(d, axis=1, dtype=jnp.int32)
    planes = ops.pack_codes(q, bits, use_pallas="pallas")
    check("pack_codes == pack_ref", same(planes, ref.pack_ref(q, bits)),
          f"{rows}x{block} int32, bits={bits}")
    back = ops.unpack_codes(planes, bits, block, use_pallas="pallas")
    check("unpack_codes round trip", same(back, q))
    check("unpack_codes == unpack_ref",
          same(back, ref.unpack_ref(planes, bits, block)))

    kv = jax.random.normal(k3, (rows, 128), jnp.bfloat16)
    for kb in (8, 4):
        codes, scales = ops.kv_quant(kv, kb, use_pallas="pallas")
        c_ref, s_ref = ref.kv_quant_ref(kv, kb)
        n_diff = int(jnp.sum(codes != c_ref))
        check(f"kv_quant bits={kb} == kv_quant_ref",
              same(codes, c_ref) and same(scales, s_ref),
              f"{rows}x128 bf16; {n_diff} codes differ")
        deq = ops.kv_dequant(codes, scales, kb, use_pallas="pallas")
        check(f"kv_dequant bits={kb} == kv_dequant_ref",
              same(deq, ref.kv_dequant_ref(codes, scales, kb)))


# ---------------------------------------------------------------------------
# four chips: sharded train step
# ---------------------------------------------------------------------------

def train_steps(cfg, rc, mesh, n_steps: int):
    """``n_steps`` of the train step on ``mesh`` (None = one chip).

    Returns (losses, optimized HLO text).  State is created directly in its
    shardings, batches come from the seeded synthetic pipeline.
    """
    rules = shd.Rules(mesh=mesh, seq_shard=rc.seq_shard, fsdp=rc.fsdp,
                      shard_vocab=rc.shard_vocab)
    with shd.use_rules(rules):
        api = model_zoo.get_api(cfg, rc)
        step = ts.make_train_step(api, cfg, rc, mesh)
        init = lambda k: ts.init_state(api, rc, k, mesh)  # noqa: E731
        if mesh is None:
            jit_init, jit_step = jax.jit(init), jax.jit(step,
                                                        donate_argnums=(0,))
        else:
            abstract = ts.abstract_state(api, rc, mesh)
            specs = ts.resolve_state_specs(
                ts.state_logical_specs(api, rc, mesh), abstract)
            sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                              specs)
            jit_init = jax.jit(init, out_shardings=sh)
            jit_step = jax.jit(step, in_shardings=(sh, None),
                               out_shardings=(sh, None), donate_argnums=(0,))
        state = jit_init(jax.random.PRNGKey(SEED))
        pipe = SyntheticPipeline(cfg, rc, seed=SEED)
        batches = [device_batch(pipe.next(), cfg, rc) for _ in range(n_steps)]
        compiled = jit_step.lower(state, batches[0]).compile()
        losses = []
        for b in batches:
            state, m = compiled(state, b)
            losses.append(float(m["loss"]))
        hlo = compiled.as_text()
    del state
    return losses, hlo


_U32_COLLECTIVE = re.compile(
    r"=\s*\(?u32\[[^=]*\s(all-gather|all-reduce|collective-permute|"
    r"all-to-all|reduce-scatter)(-start)?\(")


def u32_collectives(hlo: str) -> int:
    """Collectives in the program that move uint32 words (packed planes)."""
    return sum(1 for line in hlo.splitlines() if _U32_COLLECTIVE.search(line))


def four_chip_phase(cfg: base.ModelConfig, rc: base.RunConfig,
                    n: int) -> None:
    sharded, _ = train_steps(cfg, rc, make_mesh((2, 2), ("data", "model")),
                             n)
    single, _ = train_steps(cfg, rc, None, n)
    gap = max(abs(a - b) for a, b in zip(sharded, single))
    check("(data 2, model 2) train step == one-chip step", gap < DIST_LOSS_ATOL,
          f"losses {sharded} vs {single}; max gap {gap:.2e}")

    pods = make_mesh((2, 2), ("pod", "data"))
    plain, hlo_plain = train_steps(cfg, rc, pods, n)
    comp, hlo_comp = train_steps(
        cfg, dataclasses.replace(rc, grad_compress_bits=8), pods, n)
    print("plain losses:", plain, "8-bit losses:", comp, flush=True)
    n_plain, n_comp = u32_collectives(hlo_plain), u32_collectives(hlo_comp)
    check("quantized exchange is in the compiled 8-bit step",
          n_comp > 0 and n_plain == 0,
          f"{n_comp} uint32 collectives (plain step: {n_plain})")
    gap = max(abs(a - b) for a, b in zip(comp, plain))
    check("8-bit exchange tracks the plain exchange",
          gap < COMPRESSED_LOSS_ATOL and all(np.isfinite(comp)),
          f"max gap {gap:.2e} over {n} steps < {COMPRESSED_LOSS_ATOL:g}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--out", default=os.path.join(ROOT, ".chip_smoke"),
                    help="scratch directory (training checkpoints)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}",
          flush=True)
    log = CompileLog()
    t_start = time.perf_counter()

    # sizes: granite-8b widths everywhere; depth cut so that each phase's
    # compiled program fits one 16 GB chip (see PERF.md, Cells)
    if args.chips == 4:
        phases = [("four_chip", lambda: four_chip_phase(
            granite(2), base.RunConfig(seq_len=2048, global_batch=4,
                                       kind="train"), n=3))]
    else:
        serve_cfg = granite(8)
        print(f"serve model: granite-8b widths, 8 of 36 layers, "
              f"{serve_cfg.param_count() / 1e9:.2f}B params "
              f"({serve_cfg.param_count() * 2 / 1e9:.1f} GB bf16)", flush=True)
        phases = [
            ("serve", lambda: serve_phase(
                serve_cfg, seq_len=2048,
                prompt_lens=[128 * i for i in range(1, 9)], max_new=32)),
            ("train", lambda: train_phase(
                granite(3), base.RunConfig(seq_len=4096, global_batch=2,
                                           kind="train"),
                steps=4, out_dir=args.out)),
            ("stencil_codec", lambda: stencil_codec_phase(
                n=1 << 26, rows=32768)),
        ]
    for name, fn in phases:
        snap = log.snapshot()
        t0 = time.perf_counter()
        try:
            fn()
        except CheckFailed:
            print(f"phase {name} FAILED", flush=True)
            return 1
        print(f"phase {name} ok in {time.perf_counter() - t0:.1f}s "
              f"({log.since(snap)})", flush=True)
    print(f"total {time.perf_counter() - t_start:.1f}s "
          f"({log.since((0.0, 0, 0))})", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
