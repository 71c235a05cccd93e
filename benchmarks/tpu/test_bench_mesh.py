"""The four-chip train cell's driver, reference and collective reader on the
CPU: the driver through the harness on four host devices at a tiny size
(in a child process, which is given the devices), its control, the
reference laid out over chips against the one-chip reference, and the
reader of exposed collective time on hand-made records and on a record of
a TPU v5e run."""
import gzip
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train.granite8b-L8.4chip"
FIXTURE = os.path.join(HERE, "fixtures", CELL + ".json.gz")


def _tiny(traffic: dict) -> dict:
    return dict(traffic, seq_len=64, trace_units=2)


def rehearse() -> dict:
    """The child's work: an untraced run of the cell's traffic at a tiny
    size through ``run.run_cell`` on four host devices, and the
    bf16-parameter control's check."""
    import jax
    import test_bench_drivers as tb
    run = tb.run
    with open(os.path.join(HERE, "traffic", "4k-b8-mesh.json")) as f:
        traffic = _tiny(json.load(f))
    e2e = [tb.metric("train_tokens_per_s", "tokens/s"),
           tb.metric("setup_s", "s")]
    devices = jax.devices()[:4]
    out, _, _ = run.run_cell(
        "train.tiny-mesh", tb.TINY_LM, traffic, e2e, [], seed=2**33 + 7,
        seconds=0.5, trace_on=False, devices=devices, chip_peaks=tb.PEAKS,
        t_process=time.perf_counter())
    mod = run.load_module(os.path.join(HERE, "drivers", "train_mesh.py"),
                          "tiny_mesh")
    d = mod.Driver(tb.TINY_LM, traffic, 5, devices, param_dtype="bfloat16")
    d.setup()
    d.unit()
    control = {c[0]: c[1] for c in d.check()}
    return {"out": out, "control": control,
            "mesh": dict(d.mesh.shape), "limits": traffic["limits"]}


def test_mesh_cell_rehearsal_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, __file__], env=env, cwd=HERE,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    out = got["out"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and got["mesh"] == {"data": 2,
                                                           "model": 2}
    assert out["checks"]["window_compiles"]["value"] == 0
    assert sorted(out["metrics"]) == ["setup_s", "train_tokens_per_s"]
    # bf16 parameters lose AdamW's first updates: the change gap fails
    assert got["control"]["change_gap"] > got["limits"]["change_gap"]


def test_reference_over_chips_is_the_one_chip_reference():
    """On one device the layout is trivial and ``two_steps`` over chips
    gives what ``reference/train.py`` gives, to rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import test_bench_drivers as tb
    import weights
    from reference import train as ref1, train_mesh as refm
    from repro.configs import base
    from repro.data.pipeline import SyntheticPipeline
    from drivers import dense
    c, opt = tb.TRAIN_LM, tb.TRAIN_LM["training"]
    rc = base.RunConfig(seq_len=64, global_batch=2, kind="train",
                        param_dtype="float32")
    pipe = SyntheticPipeline(dense.model_config(c), rc, seed=3)
    batches = [pipe.next() for _ in range(3)]
    l1, g1, w1 = ref1.two_steps(weights.make(3, c, jnp.float32), batches, c,
                                opt)
    mesh = refm.make_mesh(jax.devices()[:1])
    w0 = weights.make(3, c, jnp.float32)
    lm, gm, wm = refm.two_steps(w0, batches, c, opt, mesh)
    np.testing.assert_allclose(lm, l1, rtol=1e-6)
    for k in g1:
        np.testing.assert_allclose(np.asarray(gm[k]), g1[k], rtol=1e-4,
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(wm[k]), np.asarray(w1[k]),
                                   rtol=1e-5, atol=1e-7)


def test_reference_splits_each_leaf_along_its_largest_divided_axis():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    import weights
    from reference import train_mesh as refm

    with open(os.path.join(HERE, "configs", "granite-8b-L8.json")) as f:
        c = json.load(f)
    split = refm.shardings(weights.shapes(c), AbstractMesh((4,), (refm.AXIS,)))
    chips = refm.AXIS
    assert split["w_gate"].spec == P(None, None, chips)   # (L, d, ff)
    assert split["w_down"].spec == P(None, chips, None)   # (L, ff, d)
    assert split["embed"].spec == P(chips, None)          # (V, d)
    assert split["unembed"].spec == P(None, chips)
    assert split["ln1"].spec == P(None, chips)


def _reader(name):
    import run
    return run.load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "mesh_" + name.replace(".", "_"))


HLO = """ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %all-gather.3 = f32[32]{0} all-gather(%fusion.1), dimensions={0}
  %fusion.7 = f32[8]{0} fusion(%x), kind=kCustom, calls=%all-reduce-scatter.2
  %cp = f32[8]{0} collective-permute-done(%cps)
  %while.4 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body
}
"""


def test_collectives_and_holders_from_the_compiled_text():
    coll, holders = _reader("collective_exposed.train").classify(HLO)
    assert coll == {"all-gather.3", "fusion.7", "cp"}
    assert holders == {"while.4"}


def test_exposed_collective_time_on_a_hand_made_record():
    """Device 0: a loop holds everything; the all-gather overlaps a fusion
    for 5 of its 10 ns and the permute runs alone for 10.  Device 1: the
    collective fusion runs alone for 20.  Averaged over 100 ns windows."""
    rec = {"window_ns": 100, "types": {}, "host": [],
           "devices": {"0": [["while.4", 0, 100], ["fusion.1", 0, 10],
                             ["all-gather.3", 5, 10], ["cp", 40, 10]],
                       "1": [["fusion.7", 10, 20], ["fusion.1", 50, 5]]}}
    ctx = {"trace": rec, "counts": {"steps": 1}, "hlo": lambda: HLO}
    assert _reader("collective_exposed.train").read(ctx) == 17.5
    no_collective = dict(ctx, hlo=lambda: HLO.splitlines()[0])
    assert _reader("collective_exposed.train").read(no_collective) is None


def test_readers_on_the_recorded_four_chip_trace():
    """Each of the cell's readers reads, from the record of a traced run on
    a host of four TPU v5e chips, what it read there."""
    import peaks
    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    ctx = {"trace": fx["record"], "counts": fx["counts"],
           "peaks": peaks.PEAKS["TPU v5 lite"], "chips": fx["chips"],
           "hlo": lambda: fx["hlo"]}
    assert len(fx["record"]["devices"]) == fx["chips"] == 4
    want = dict(fx["metrics"], **fx["chip_metrics"])
    assert "collective_exposed.train" in want
    for metric, value in want.items():
        assert _reader(metric).read(ctx) == pytest.approx(value, rel=1e-9), \
            metric


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    print(json.dumps(rehearse()))
