"""Subprocess body for tests/test_distributed.py — runs with 8 host devices.

Invoked as:  python tests/_distributed_main.py <scenario>

Scenarios:
  compressed_grads  — multi-pod mesh, compressed vs plain cross-pod gradient
                      exchange: losses must track closely (error feedback)
  remesh            — train on mesh A, checkpoint, restore on mesh B
                      (elastic re-mesh), losses must continue identically
  dist_equivalence  — sharded (2,2) mesh train step == single-device step
  flash_heads       — the flash kernel per shard on a (2,4) mesh, KV heads
                      dividing 'model', split across it, or replicated:
                      output and gradients == the unsharded call
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import base                       # noqa: E402
from repro.data.pipeline import SyntheticPipeline, device_batch  # noqa: E402
from repro.distributed import sharding as shd        # noqa: E402
from repro.launch.mesh import make_mesh              # noqa: E402
from repro.models import model_zoo                   # noqa: E402
from repro.train import step as ts                   # noqa: E402
from repro.train.loop import LoopConfig, train       # noqa: E402


def _run_steps(cfg, rc, mesh, n_steps, seed=0):
    rules = shd.Rules(mesh=mesh, seq_shard=rc.seq_shard, fsdp=rc.fsdp)
    with shd.use_rules(rules):
        api = model_zoo.get_api(cfg, rc)
        fn = jax.jit(ts.make_train_step(api, cfg, rc, mesh))
        state = ts.init_state(api, rc, jax.random.PRNGKey(seed), mesh)
        pipe = SyntheticPipeline(cfg, rc, seed=3)
        losses = []
        for _ in range(n_steps):
            batch = device_batch(pipe.next(), cfg, rc)
            state, m = fn(state, batch)
            losses.append(float(jax.device_get(m["loss"])))
    return losses, state


def scenario_compressed_grads():
    cfg = base.load_smoke("tinyllama-1.1b")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rc0 = base.RunConfig(seq_len=64, global_batch=8, kind="train",
                         remat=False, q_block=32, kv_block=32, lr=1e-3,
                         grad_compress_bits=0)
    rc8 = base.RunConfig(seq_len=64, global_batch=8, kind="train",
                         remat=False, q_block=32, kv_block=32, lr=1e-3,
                         grad_compress_bits=8)
    plain, _ = _run_steps(cfg, rc0, mesh, 20)
    comp, _ = _run_steps(cfg, rc8, mesh, 20)
    print("plain last:", plain[-1], "compressed last:", comp[-1])
    assert comp[-1] < plain[0] - 0.2, "compressed run failed to learn"
    assert abs(comp[-1] - plain[-1]) < 0.35, (comp[-1], plain[-1])
    # 16-bit compression must track essentially exactly
    rc16 = base.RunConfig(seq_len=64, global_batch=8, kind="train",
                          remat=False, q_block=32, kv_block=32, lr=1e-3,
                          grad_compress_bits=16)
    comp16, _ = _run_steps(cfg, rc16, mesh, 20)
    assert abs(comp16[-1] - plain[-1]) < 0.1, (comp16[-1], plain[-1])
    print("OK compressed_grads")


def scenario_remesh():
    cfg = base.load_smoke("tinyllama-1.1b")
    rc = base.RunConfig(seq_len=64, global_batch=8, kind="train",
                        remat=False, q_block=32, kv_block=32, lr=1e-3)
    with tempfile.TemporaryDirectory() as d:
        loop = LoopConfig(total_steps=10, ckpt_every=5, ckpt_dir=d)
        mesh_a = make_mesh((4, 2), ("data", "model"))
        h1 = train(cfg, rc, loop, mesh=mesh_a, log_every=0)
        # resume the SAME run on a different device organization
        mesh_b = make_mesh((2, 4), ("data", "model"))
        loop2 = LoopConfig(total_steps=20, ckpt_every=5, ckpt_dir=d)
        h2 = train(cfg, rc, loop2, mesh=mesh_b, log_every=0)
        # reference: uninterrupted single-mesh run
        with tempfile.TemporaryDirectory() as d2:
            ref = train(cfg, rc, LoopConfig(total_steps=20, ckpt_every=5,
                                            ckpt_dir=d2),
                        mesh=mesh_a, log_every=0)
        got, want = h2["loss"][-3:], ref["loss"][-3:]
        print("remesh tail:", got, "ref tail:", want)
        assert np.allclose(got, want, atol=5e-3), (got, want)
    print("OK remesh")


def scenario_dist_equivalence():
    cfg = base.load_smoke("yi-9b")
    rc = base.RunConfig(seq_len=64, global_batch=8, kind="train",
                        remat=False, q_block=32, kv_block=32, lr=1e-3)
    mesh = make_mesh((2, 2), ("data", "model"))
    dist, _ = _run_steps(cfg, rc, mesh, 5)
    single, _ = _run_steps(cfg, rc, None, 5)
    print("dist:", dist, "single:", single)
    assert np.allclose(dist, single, atol=5e-3), (dist, single)
    print("OK dist_equivalence")


def scenario_flash_heads():
    from repro.models import layers
    mesh = make_mesh((2, 4), ("data", "model"))
    B, S, D = 2, 128, 64
    kw = dict(causal=True, window=0, bq=64, bk=64, interpret=True)

    def loss(q, k, v):
        o = layers._flash_per_shard(q, k, v, **kw)
        return jnp.sum(o * jnp.cos(o)), o

    # (KV, G): KV divides model=4; one KV head per 2 shards; one for all 4;
    # 6 query heads do not divide 4 (every shard computes every head)
    for KV, G in [(4, 2), (2, 4), (1, 8), (3, 2)]:
        rng = np.random.default_rng(KV * 10 + G)
        q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in
                   [(B, S, KV * G, D), (B, S, KV, D), (B, S, KV, D)])
        # a fresh jit each time: the rules are read when the call is traced
        fn = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
            loss, argnums=(0, 1, 2), has_aux=True))
        (_, o_ref), g_ref = fn()(q, k, v)
        with shd.use_rules(shd.Rules(mesh=mesh)):
            (_, o), g = fn()(q, k, v)
        for name, a, b in zip("o dq dk dv".split(), (o, *g), (o_ref, *g_ref)):
            rel = float(jnp.abs(a - b).max() / jnp.abs(b).max())
            assert rel < 1e-5, (KV, G, name, rel)
    print("OK flash_heads")


if __name__ == "__main__":
    {
        "compressed_grads": scenario_compressed_grads,
        "remesh": scenario_remesh,
        "dist_equivalence": scenario_dist_equivalence,
        "flash_heads": scenario_flash_heads,
    }[sys.argv[1]]()
