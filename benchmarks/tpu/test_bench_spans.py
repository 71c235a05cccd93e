"""The program's device scopes (``kv_cache_write``, ``adamw_update``) as the
benchmark finds them: in the CPU-compiled serve and train steps, in the
readers of ``kv_write_ms.serve`` and ``adamw_ms.train`` on hand-made
records, and a program span labelling an idle gap."""
import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

import devtrace
import test_bench_drivers as tb
from drivers import dense
from drivers import train as train_driver
from repro.configs import base
from repro.serve.engine import ServeEngine

run = tb.run


def reader(metric):
    return run.load_module(os.path.join(run.HERE, "metrics", metric + ".py"),
                           "spans_" + metric.replace(".", "_"))


def decode_step_hlo(bits: int) -> str:
    cfg = dense.model_config(tb.TINY_LM)
    rc = base.RunConfig(seq_len=32, global_batch=4, kind="decode",
                        kv_cache_bits=bits)
    eng = ServeEngine(cfg, rc)
    state = jax.eval_shape(lambda: eng.api.init_decode_state(4))
    tok = jax.ShapeDtypeStruct((4,), jnp.int32)
    return eng._step.lower(eng.params, state, tok).compile().as_text()


@pytest.mark.parametrize("bits", [8, 16])
def test_cache_write_scope_in_the_compiled_decode_step(bits):
    """The cache write's ops carry a scope of their own, beside the dequant
    and attention that ``kv_attn_ms.serve`` reads.  The CPU's compiler may
    fuse the int8 cache's scatter into the dequant's first fusion, which
    then holds both; the TPU's keeps them apart (the fixture test below)."""
    hlo = decode_step_hlo(bits)
    write = devtrace.scoped_instructions(hlo, "kv_cache_write")
    interior = devtrace.scoped_instructions(hlo, "decode_attn_interior")
    assert write - interior and interior - write
    if bits == 16:
        assert not write & interior


def _fixture_hlo(cell: str) -> str:
    with gzip.open(os.path.join(run.HERE, "fixtures", cell + ".spans.json.gz"),
                   "rt") as f:
        return json.load(f)["hlo"]


def test_cache_write_scope_in_the_tpu_compiled_decode_step():
    """In the step the chip ran, the scatter's serial loops carry the scope
    themselves and nothing of the write is in ``decode_attn_interior``."""
    hlo = _fixture_hlo("serve.granite8b-L8.int8-batch")
    write = devtrace.scoped_instructions(hlo, "kv_cache_write")
    interior = devtrace.scoped_instructions(hlo, "decode_attn_interior")
    assert len({n for n in write if n.startswith("while")}) == 4
    assert interior and not write & interior


def test_adamw_scope_in_the_compiled_train_step():
    drv = train_driver.Driver(tb.TRAIN_LM, tb.TRAIN, 5, jax.devices()[:1])
    drv.setup()
    hlo = drv.hlo_text()
    assert devtrace.scoped_instructions(hlo, "adamw_update")
    assert not devtrace.scoped_instructions(hlo, "kv_cache_write")


def _hlo(scope: str) -> str:
    """An optimized module whose loop ``while.22`` carries ``scope`` and
    whose ``fusion.9`` calls a computation that holds an op of it."""
    return f"""HloModule jit_step

%fused_computation.3 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %multiply.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="jit(step)/{scope}/mul"}}
}}

ENTRY %main.9 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.9 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.3
  %dot.2 = f32[8]{{0}} multiply(%a, %a), metadata={{op_name="jit(step)/other/mul"}}
  ROOT %while.22 = f32[8]{{0}} while(%fusion.9), condition=%c, body=%b, metadata={{op_name="jit(step)/while/body/{scope}/scatter"}}
}}
"""


REC = {"window_ns": 10_000_000,
       "devices": {"0": [["fusion.9", 0, 1_000_000],
                         ["while.22", 2_000_000, 3_000_000],
                         ["fusion.11", 2_500_000, 1_000_000],   # its body
                         ["dot.2", 6_000_000, 2_000_000]]},
       "types": {}, "host": [["bench/window", 0, 10_000_000]]}

CASES = [("kv_write_ms.serve", "kv_cache_write", "decode_steps"),
         ("adamw_ms.train", "adamw_update", "steps")]


@pytest.mark.parametrize("metric,scope,steps", CASES)
def test_scope_reader_on_a_hand_made_record(metric, scope, steps):
    """ms per step of the scope's ops, a loop counted whole with its body."""
    ctx = {"trace": REC, "counts": {steps: 2}, "hlo": lambda: _hlo(scope)}
    assert reader(metric).read(ctx) == pytest.approx((1 + 3) / 2)


@pytest.mark.parametrize("metric,scope,steps", CASES)
def test_scope_reader_reads_nothing_where_there_is_nothing(metric, scope,
                                                          steps):
    read = reader(metric).read
    ctx = {"trace": REC, "counts": {steps: 2}, "hlo": lambda: _hlo(scope)}
    # a program without the scope, as the parent commit compiles it
    assert read(dict(ctx, hlo=lambda: _hlo("some_other_scope"))) is None
    # no step counted, or no device plane (a CPU trace)
    assert read(dict(ctx, counts={})) is None
    assert read(dict(ctx, trace=dict(REC, devices={}))) is None


def test_idle_gap_inside_a_program_span_takes_its_name():
    """A gap is labelled by the innermost host span it falls in: a span of
    the engine inside ``serve/generate``, not the outer call."""
    rec = {"window_ns": 1000,
           "devices": {"0": [["fusion.1", 0, 400], ["fusion.2", 500, 500]]},
           "types": {},
           "host": [["bench/window", 0, 1000], ["serve/generate", 0, 1000],
                    ["serve/dispatch", 0, 20], ["serve/fetch", 20, 400],
                    ["serve/select", 420, 80], ["serve/dispatch", 500, 20]]}
    assert devtrace.idle_gaps(rec) == [["serve/select", 100 / 1e9]]
