"""The program's spans in the ``jax.profiler`` trace: an ``obs.span`` writes a
host event on the profiler's clock whether obs is enabled or not, and the
serve engine and the data feed open theirs once per step and per batch."""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import base
from repro.data.pipeline import SyntheticPipeline, device_batch
from repro.serve.engine import ServeEngine

WINDOW = "bench/window"


def host_events(logdir: str, prefixes):
    """[(name, start ns, duration ns)] of the trace's host events whose
    names start with one of ``prefixes``, in order of their start."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.duration_ns)
                   for plane in data.planes for line in plane.lines
                   for e in line.events if e.name.startswith(tuple(prefixes))),
                  key=lambda e: e[1])


@pytest.fixture
def disabled():
    prev = obs.enabled()
    obs.disable()
    yield
    if prev:
        obs.enable()


@pytest.mark.parametrize("enabled", [False, True])
def test_span_is_in_the_profiler_trace(enabled, disabled, tmp_path):
    """Inside the harness's window, on the same clock, by name alone; with
    obs enabled the ``Tracer`` records it too."""
    tracer = obs.Tracer()
    with (obs.enabled_scope(tracer=tracer) if enabled
          else contextlib.nullcontext()):
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(WINDOW):
                with obs.span("serve/select", batch=4):
                    pass
    ev = host_events(str(tmp_path), ["bench/", "serve/"])
    assert [e[0] for e in ev] == [WINDOW, "serve/select"]
    (_, w0, wd), (_, s0, sd) = ev
    assert w0 <= s0 and s0 + sd <= w0 + wd
    assert [(r.name, r.args) for r in tracer.records] == (
        [("serve/select", {"batch": 4})] if enabled else [])


def test_disabled_span_is_the_shared_null_outside_a_session(disabled,
                                                            tmp_path):
    """No profiler session, nothing to record: the shared null context."""
    assert obs.span("a") is obs.span("b")
    with jax.profiler.trace(str(tmp_path)):
        assert obs.span("a") is not obs.span("b")
        with obs.span("a") as sp:
            sp.add_cycles(3)           # the disabled span's handle is inert


def test_generate_opens_three_spans_per_decode_step(disabled, tmp_path):
    cfg = base.load_smoke("granite-8b")
    rc = base.RunConfig(seq_len=32, global_batch=3, kind="decode",
                        kv_cache_bits=8)
    eng = ServeEngine(cfg, rc)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    max_new = 4
    eng.generate(prompts, max_new=max_new)             # compiles outside
    with jax.profiler.trace(str(tmp_path)):
        out = eng.generate(prompts, max_new=max_new)
    steps = max(len(p) for p in prompts) + max_new - 1
    assert [len(o) for o in out] == [max_new] * len(prompts)
    ev = host_events(str(tmp_path), ["serve/"])
    names = [e[0] for e in ev]
    assert names == ["serve/generate"] + ["serve/dispatch", "serve/fetch",
                                          "serve/select"] * steps
    g0, gd = ev[0][1], ev[0][2]
    assert all(g0 <= s and s + d <= g0 + gd for _, s, d in ev[1:])


def test_feed_opens_its_spans(disabled, tmp_path):
    cfg = base.load_smoke("tinyllama-1.1b")
    rc = base.RunConfig(seq_len=16, global_batch=2, kind="train")
    pipe = SyntheticPipeline(cfg, rc, seed=1)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            batch = device_batch(pipe.next(), cfg, rc)
    assert np.asarray(batch["tokens"]).shape == (2, 16)
    names = [e[0] for e in host_events(str(tmp_path), ["data/"])]
    assert names == ["data/next", "data/to_device"] * 2
