"""CPU rehearsal of each driver through the harness, at a tiny size.

``run.py`` refuses a missing TPU, so these tests call its ``run_cell`` with
tiny configurations and the CPU device, and check that a run builds a
result line with exactly the contract's keys and comes out correct.
"""
import importlib.util
import os
import time

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location("tpu_bench_run",
                                               os.path.join(HERE, "run.py"))
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)

import peaks  # noqa: E402  (run.py put the harness on the path)

TINY_LM = {"name": "tiny-dense", "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
           "rms_norm_eps": 1e-5, "rope_theta": 10000,
           "tie_word_embeddings": False}
# tiny serve on the CPU: sound runs read gaps of 0 to 0.017, the int4-cache
# control 0.11 to 0.27 (seeds 11-13)
SERVE = {"driver": "serve", "requests_per_call": 4, "prompt_min": 4,
         "prompt_max": 12, "new_tokens": 8, "kv_cache_bits": 8,
         "cache_len": 32, "check_requests": 2, "trace_steps": 4,
         "limits": {"max_logit_gap": 0.05}}
STENCIL_CFG = {"name": "tiny-jacobi", "cells": 4096}
# in interpret mode on the CPU one side's division by 3 becomes a multiply
# (sound runs read 2e-7 to 9e-6, the bf16 control 9e-3 to 1.3e-2)
STENCIL = {"driver": "stencil", "t_steps": 8, "width": 512,
           "use_pallas": "interpret", "ahead": 1, "trace_units": 2,
           "limits": {"max_abs_error": 1e-4}}
TRAIN_LM = dict(TINY_LM, training={
    "param_dtype": "float32", "opt_dtype": "float32", "lr": 3e-4,
    "warmup_steps": 100, "total_steps": 10000, "b1": 0.9, "b2": 0.95,
    "eps": 1e-8, "weight_decay": 0.1, "decay_min_rank": 2, "grad_clip": 1.0})
TRAIN = {"driver": "train", "batch": 2, "seq_len": 64, "trace_units": 2,
         "limits": {"loss_gap": 1e-3, "first_grad_gap": 1e-2,
                    "change_gap": 1e-2}}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
PEAKS = peaks.PEAKS["TPU v5 lite"]


def metric(name, unit, moves=None):
    m = {"name": name, "unit": unit}
    if moves:
        m["moves"] = moves
    return m


CASES = {
    "serve": (TINY_LM, SERVE, [metric("serve_tokens_per_s", "tokens/s")],
              [metric("mfu.serve", "%"), metric("device_idle.serve", "%")]),
    "stencil": (STENCIL_CFG, STENCIL,
                [metric("stencil_updates_per_s", "updates/s")],
                [metric("jacobi_roofline", "%"),
                 metric("device_idle.stencil", "%")]),
    "train": (TRAIN_LM, TRAIN, [metric("train_tokens_per_s", "tokens/s")],
              [metric("mfu.train", "%"), metric("device_idle.train", "%")]),
}


@pytest.mark.parametrize("trace_on", [False, True])
@pytest.mark.parametrize("kind", sorted(CASES))
def test_cell_rehearsal(kind, trace_on, tmp_path):
    config, traffic, e2e, layer = CASES[kind]
    e2e = e2e + [metric("setup_s", "s")]
    out, setup_line, checks = run.run_cell(
        f"{kind}.tiny", config, traffic, e2e, layer, seed=2**33 + 7,
        seconds=0.5, trace_on=trace_on, devices=jax.devices()[:1],
        chip_peaks=PEAKS, t_process=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert set(out) == set(KEYS) | {"checks"} | ({"breakdown"} if trace_on
                                                 else set())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["count"] == 1
    if trace_on:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # no TPU plane on the CPU: the device readers find nothing to read
        assert "device_idle." + kind not in out["metrics"]
    else:
        assert sorted(out["metrics"]) == sorted(m["name"] for m in e2e)
        assert all(v["value"] > 0 for v in out["metrics"].values())
    assert "compilations in the window: 0" in setup_line
