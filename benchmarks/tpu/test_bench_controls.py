"""The comparison that decides ``correct`` fails what it has to fail.

At the tiny sizes of ``test_bench_drivers``, on the CPU: each cell's
control (the next precision down, as ``calibrate.py --control`` runs it on
the chip) and each fault that the cell can have, planted under the timed
path while the harness drives the rest of a run, must come out not
correct.  The same tiny limits let sound runs through in
``test_bench_drivers``.
"""
import json
import time

import jax
import jax.numpy as jnp
import pytest

import test_bench_drivers as tb
from calibrate import CONTROLS


def run_tiny(kind, **driver_kw):
    config, traffic, e2e, layer = tb.CASES[kind]
    if driver_kw:
        mod = tb.run.load_module(f"{tb.HERE}/drivers/{kind}.py", "ctl_" + kind)
        d = mod.Driver(config, traffic, 2**40 + 3, jax.devices()[:1],
                       **driver_kw)
        d.setup()
        d.unit()
        return d.check()
    out, _, checks = tb.run.run_cell(
        f"{kind}.tiny", config, traffic, e2e + [tb.metric("setup_s", "s")],
        layer, seed=2**40 + 3, seconds=0.2, trace_on=False,
        devices=jax.devices()[:1], chip_peaks=tb.PEAKS,
        t_process=time.perf_counter())
    return checks


@pytest.mark.parametrize("kind", sorted(tb.CASES))
def test_control_is_not_correct(kind):
    checks = run_tiny(kind, **CONTROLS[kind])
    assert not all(tb.run.passes(c) for c in checks), checks


def test_sound_tiny_runs_are_correct():
    for kind in sorted(tb.CASES):
        checks = run_tiny(kind)
        assert all(tb.run.passes(c) for c in checks), (kind, checks)


def committed_limits(traffic: str) -> dict:
    with open(f"{tb.HERE}/traffic/{traffic}.json") as f:
        return json.load(f)["limits"]


def serve_limit():
    return committed_limits("int8-batch")["max_logit_gap"]


@pytest.mark.parametrize("kind,traffic", [("stencil", "t8-passes"),
                                          ("train", "4k-b1")])
def test_control_fails_the_committed_limits(kind, traffic):
    config, tiny, _, _ = tb.CASES[kind]
    mod = tb.run.load_module(f"{tb.HERE}/drivers/{kind}.py", "cmt_" + kind)
    d = mod.Driver(config, dict(tiny, limits=committed_limits(traffic)),
                   2**40 + 3, jax.devices()[:1], **CONTROLS[kind])
    d.setup()
    d.unit()
    checks = d.check()
    assert not all(tb.run.passes(c) for c in checks), checks


# heads of granite's width (128), d 512, vocab 8192, one layer: on the CPU
# sound runs read 0.019 to 0.040 and the int4 control 0.53 to 1.35 (seeds 1-6)
SERVE_WIDE = dict(tb.TINY_LM, name="wide-dense", hidden_size=512,
                  intermediate_size=1024, num_attention_heads=4,
                  num_key_value_heads=1, head_dim=128, num_hidden_layers=1,
                  vocab_size=8192)


@pytest.mark.parametrize("control,seed", [(False, 3), (False, 5),
                                          (True, 1), (True, 6)])
def test_serve_limit_as_committed_separates_the_control(control, seed):
    """The serve cell's committed limit passes sound runs and fails the
    int4-cache control at granite's head width and a vocabulary of 8192."""
    traffic = dict(tb.SERVE, new_tokens=32, cache_len=44, check_requests=4,
                   limits={"max_logit_gap": serve_limit()})
    mod = tb.run.load_module(f"{tb.HERE}/drivers/serve.py", "wide_serve")
    d = mod.Driver(SERVE_WIDE, traffic, seed, jax.devices()[:1],
                   **(CONTROLS["serve"] if control else {}))
    d.setup()
    d.unit()
    checks = d.check()
    assert all(tb.run.passes(c) for c in checks) != control, checks


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve import engine
    generate = engine.ServeEngine.generate

    def altered(self, prompts, max_new=16, greedy=True):
        out = generate(self, prompts, max_new=max_new, greedy=greedy)
        return [[(o[0] + 1) % self.cfg.vocab] + o[1:] for o in out]

    monkeypatch.setattr(engine.ServeEngine, "generate", altered)
    checks = run_tiny("serve")
    assert not all(tb.run.passes(c) for c in checks), checks


def test_altered_cell_is_not_correct(monkeypatch):
    from repro.kernels import ops
    tiled = ops.jacobi1d_tiled

    def altered(x, t_steps, width=512, use_pallas="auto"):
        y = tiled(x, t_steps, width=width, use_pallas=use_pallas)
        return y.at[y.shape[0] // 3].add(jnp.float32(1e-3))

    monkeypatch.setattr(ops, "jacobi1d_tiled", altered)
    checks = run_tiny("stencil")
    assert not all(tb.run.passes(c) for c in checks), checks


def test_unchanged_state_is_not_correct(monkeypatch):
    from repro.train import step as ts
    make = ts.make_train_step

    def frozen(*args, **kwargs):
        step = make(*args, **kwargs)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return train_step

    monkeypatch.setattr(ts, "make_train_step", frozen)
    checks = run_tiny("train")
    assert not all(tb.run.passes(c) for c in checks), checks
