"""The MoE serve cell's driver, controls and counts, on the CPU at a tiny
size: a run through the harness comes out correct, and each control that
``calibrate_moe.py`` plants on the chip (the capacity-dropping expert
layer, top-1 routing, the int4 KV cache) comes out not correct."""
import json
import time

import jax
import numpy as np
import pytest

import calibrate_moe
import counts_moe
import test_bench_drivers as tb

run = tb.run

TINY_MOE = dict(tb.TINY_LM, name="tiny-moe", num_local_experts=8,
                num_experts_per_tok=2, expert_share=[0, 8])
# 16 requests of 4-12 prompt tokens and 8 new, all compared, router
# margin 0.05: on the CPU sound runs read gaps of 0 to 0.62 (seeds 1-12,
# and a run of several calls) and the controls 0.94 to 3.23 (seeds 1-12:
# capacity 1.21-3.23, top-1 2.02-3.14, int4 cache 0.94-2.39)
with open(f"{tb.HERE}/traffic/int8-batch-moe.json") as _f:
    SERVE_MOE = dict(json.load(_f), requests_per_call=16, prompt_min=4,
                     prompt_max=12, new_tokens=8, cache_len=32,
                     check_requests=16, trace_steps=4, router_margin=0.05,
                     limits={"max_logit_gap": 0.75, "near_tie_positions": 64})


def tiny_driver(seed, control=None):
    mod = run.load_module(f"{tb.HERE}/drivers/serve_moe.py", "tiny_moe")
    with calibrate_moe.planted(control):
        d = mod.Driver(TINY_MOE, SERVE_MOE, seed, jax.devices()[:1],
                       **calibrate_moe.DRIVER_KW.get(control, {}))
        d.setup()
        d.unit()
    return d


@pytest.mark.parametrize("trace_on", [False, True])
def test_moe_cell_rehearsal(trace_on, tmp_path):
    e2e = [tb.metric("serve_tokens_per_s", "tokens/s"),
           tb.metric("setup_s", "s")]
    layer = [tb.metric(m, u) for m, u in [("moe_experts_ms.serve", "ms"),
                                          ("moe_route_ms.serve", "ms"),
                                          ("expert_roofline", "%")]]
    out, setup_line, checks = run.run_cell(
        "serve.tiny-moe", TINY_MOE, SERVE_MOE, e2e, layer, seed=2**33 + 7,
        seconds=0.5, trace_on=trace_on, devices=jax.devices()[:1],
        chip_peaks=tb.PEAKS, t_process=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 16 and out["failed"] == 0
    assert set(out["checks"]) == {"max_logit_gap", "near_tie_positions",
                                  "malformed_requests", "compared_tokens",
                                  "window_compiles"}
    if trace_on:
        # no TPU plane on the CPU: the device readers find nothing to read
        assert out["metrics"] == {}
    else:
        assert sorted(out["metrics"]) == ["serve_tokens_per_s", "setup_s"]
    assert "compilations in the window: 0" in setup_line


@pytest.mark.parametrize("seed", [1, 2])
def test_sound_moe_runs_are_correct(seed):
    checks = tiny_driver(seed).check()
    assert all(run.passes(c) for c in checks), checks


@pytest.mark.parametrize("control", calibrate_moe.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2])
def test_moe_control_is_not_correct(control, seed):
    checks = tiny_driver(seed, control).check()
    assert not all(run.passes(c) for c in checks), (control, checks)


def test_planted_layer_is_removed_again():
    from repro.models import moe
    dropless = moe.moe_block
    with calibrate_moe.planted("capacity"):
        assert moe.moe_block is calibrate_moe.capacity_moe_block
    assert moe.moe_block is dropless


def test_near_ties_are_left_out_and_counted():
    d = tiny_driver(1)
    d.check()
    gaps, margins = d.gaps, d.margins
    assert gaps.shape == margins.shape == (16, 8)
    table = calibrate_moe.sweep(gaps, margins)
    tie = margins < SERVE_MOE["router_margin"]
    assert table["16r/0.05"] == [float(gaps[~tie].max()), int(tie.sum())]
    assert table["4r/0.0"][1] == 0          # 2 of 8 experts: never a tie at 0


def test_expert_counts_at_the_cell_size():
    """Mixtral's grouped matmuls at a decode batch of 256: 512 routed rows
    per layer, 6 d ff FLOPs each; every expert's weights read once."""
    with open(f"{tb.HERE}/configs/mixtral-8x7b-L4.json") as f:
        c = json.load(f)
    d, ff, L = 4096, 14336, 4
    flops, nbytes = counts_moe.expert_step(c, 256)
    assert counts_moe.routed_rows(c, 256) == 512
    assert flops == 6 * d * ff * 512 * L
    assert nbytes == L * (3 * 8 * d * ff * 2 + 512 * (3 * d + 3 * ff) * 2)
    least = max(flops / tb.PEAKS.flops, nbytes / tb.PEAKS.hbm_bytes)
    assert least == pytest.approx(0.01404, rel=1e-3)     # bound by bytes
    half = dict(c, expert_share=[4, 4])
    assert counts_moe.routed_rows(half, 256) == 256
    assert np.isclose(counts_moe.expert_step(half, 256)[1],
                      L * (3 * 4 * d * ff * 2 + 256 * (3 * d + 3 * ff) * 2))


def test_decode_step_counts_at_the_cell_size():
    """A whole decode step of the MoE decoder at batch 256 and 300 cached
    positions: the dense decoder's count with each layer's MLP replaced by
    the router and the 8 held experts, each expert's weights read once."""
    import counts
    with open(f"{tb.HERE}/configs/mixtral-8x7b-L4.json") as f:
        c = json.load(f)
    d, ff, L, E, B = 4096, 14336, 4, 8, 256
    attn = 2 * d * 4096 + 2 * d * 1024
    head = d * 32000
    flops, nbytes = counts_moe.decode_step(c, B, 300, 8)
    want_flops = B * (2 * (L * (attn + d * E) + head)
                      + L * 4 * 300 * 4096) + 6 * d * ff * 2 * B * L
    assert flops == pytest.approx(want_flops, rel=1e-12)
    cache = B * L * 8 * 2 * (128 + 4) * 301
    want_bytes = 2 * (L * (attn + d * E + E * 3 * d * ff) + head) \
        + B * d * 2 + cache
    assert nbytes == pytest.approx(want_bytes, rel=1e-12)
    least = counts.least_seconds(flops, nbytes, tb.PEAKS)
    assert least == pytest.approx(0.01529, rel=1e-3)     # bound by bytes
