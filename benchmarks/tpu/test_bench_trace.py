"""The reduction from a trace to numbers, on hand-made records and on
records of TPU runs (``fixtures/``)."""
import base64
import gzip
import json
import os

import pytest

import devtrace
import peaks
import test_bench_drivers as tb

run = tb.run

REC = {
    "window_ns": 1000,
    "devices": {"0": [["fusion.1", 0, 100],
                      ["while.2", 200, 300],          # holds the next two
                      ["fusion.3", 210, 50],
                      ["custom-call.5", 300, 100],
                      ["fusion.4", 900, 200]],       # runs past the end
                "1": [["fusion.1", 0, 500]]},
    "types": {"fusion.1": "bf16[8,128]"},
    "host": [["bench/window", 0, 1000], ["bench/step", 0, 600],
             ["bench/batch", 600, 350]],
}


def test_busy_is_the_union_of_ops_in_the_window():
    assert devtrace.busy_ns(REC["devices"]["0"], 1000) == 100 + 300 + 100
    # averaged over the two devices
    assert devtrace.busy_s(REC) == (500 + 500) / 2 / 1e9
    assert devtrace.idle_share(REC) == 0.5


def test_op_seconds_and_top_ops():
    secs = devtrace.op_seconds(REC, lambda n: n in {"fusion.3", "while.2"})
    assert secs == 300 / 2 / 1e9        # the loop holds fusion.3: a union
    top = devtrace.top_ops(REC, 2)
    assert top[0] == ["fusion.1 bf16[8,128]", 600 / 2 / 1e9]
    assert top[1][0] == "while.2"


def test_idle_gaps_by_host_span():
    gaps = dict(devtrace.idle_gaps(REC))
    # device 0 idles in [100, 200) under bench/step, [500, 900) split by
    # its midpoint 700 into bench/batch
    assert gaps == {"bench/step": 100 / 1e9, "bench/batch": 400 / 1e9}


HLO = """HloModule jit_step

%fused_computation.7 (param_0: bf16[8]) -> bf16[8] {
  %param_0 = bf16[8]{0} parameter(0)
  ROOT %convert.1 = bf16[8]{0} convert(%param_0), metadata={op_name="jit(step)/while/body/decode_attn_interior/convert_element_type"}
}

%body.3 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/while/body/mul"}
}

ENTRY %main.9 (a: bf16[8]) -> bf16[8] {
  %a = bf16[8]{0} parameter(0)
  %dot.2 = bf16[8]{0} multiply(%a, %a), metadata={op_name="jit(step)/decode_attn_interior/dot_general"}
  ROOT %while.5 = bf16[8]{0} while(%dot.2), condition=%cond.4, body=%body.3
}
"""


def test_scoped_instructions_follow_fusions_not_loops():
    names = devtrace.scoped_instructions(HLO, "decode_attn_interior")
    assert names == {"fusion.7", "dot.2", "convert.1"}


@pytest.mark.parametrize("event,want", [
    ("%multiply_convert_fusion.4 = bf16[256,576,8,128]{3,2,1,0:T(8,128)(2,1)}"
     " fusion(s8[256,576,8,128]{3,2,1,0} %gte.576), kind=kLoop,"
     " calls=%fused_computation.16.clone.clone",
     ("multiply_convert_fusion.4", "bf16[256,576,8,128]")),
    ("%while.22 = (s32[]{:T(128)}, s8[256,576,8,128]{3,2,1,0}) while(%t),"
     " condition=%cond.2, body=%body.2", ("while.22", "s32[]")),
    ("_fwd_kernel", ("_fwd_kernel", "")),
])
def test_op_name_of_an_hlo_text_event(event, want):
    """A TPU ops-line event is named by its instruction's text; the record
    keeps the instruction's name, which the compiled module's text uses."""
    assert devtrace.op_name(event) == want


def _pallas_line(name: str, kernel: str) -> str:
    body = base64.b64encode(b"ML\xefR\x00loc(" + kernel.encode()
                            + b")\x00").decode()
    return (f"  %{name} = bf16[1,2,256,128]{{3,2,1,0}} custom-call(%a, %b), "
            f'custom_call_target="tpu_custom_call", metadata={{}}, '
            f'backend_config={{"custom_call_config":{{"body":"{body}",'
            f'"needs_layout_passes":true}}}}')


def test_kernel_instructions_by_the_kernel_in_the_body():
    """Pallas calls are named after the transformations around them; the
    kernel's function name is found in the serialized body."""
    hlo = "\n".join([_pallas_line("jvp__.1", "_fwd_kernel"),
                     _pallas_line("checkpoint.20", "_bwd_dkv_kernel"),
                     _pallas_line("rematted_computation.10", "_fwd_kernel"),
                     "  %fusion.3 = bf16[8]{0} fusion(%jvp__.1), kind=kLoop"])
    assert devtrace.kernel_instructions(hlo, "_fwd_kernel") == {
        "jvp__.1", "rematted_computation.10"}
    assert devtrace.kernel_instructions(hlo, "_bwd_dkv_kernel") == {
        "checkpoint.20"}
    assert devtrace.kernel_instructions(hlo, "_bwd_dq_kernel") == set()


def test_saved_record_reads_back(tmp_path):
    path = str(tmp_path / "t.json.gz")
    devtrace.save(REC, path)
    assert devtrace.read(path) == REC


FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.mark.parametrize("name", sorted(os.listdir(FIXTURES)))
def test_readers_on_a_recorded_tpu_trace(name):
    """Each per-layer reader reads, from a record of a TPU v5e run (with its
    counts and the compiled step's text), the value it read there."""
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        fx = json.load(f)
    ctx = {"trace": fx["record"], "counts": fx["counts"],
           "peaks": peaks.PEAKS["TPU v5 lite"], "chips": 1,
           "hlo": lambda: fx["hlo"]}
    for metric, want in fx["metrics"].items():
        reader = run.load_module(os.path.join(run.HERE, "metrics",
                                              metric + ".py"), "fx_" + metric)
        assert reader.read(ctx) == pytest.approx(want, rel=1e-9), metric
