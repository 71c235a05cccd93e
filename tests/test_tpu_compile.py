"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Interpret mode checks what a kernel computes, not whether the chip's
compiler accepts it: block shapes off the (8, 128) tiling, reshapes that
split the lane dimension and unsigned reductions all pass interpret mode
and are refused by the TPU compiler.  These tests compile each kernel for a
described (not attached) v5e chip and check that the program holds the
kernel.  Nothing runs, so they say nothing about results or speed.
"""
import functools

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import bitplane, jacobi_mars, kvpack
from repro.kernels.flash_attention import flash_attention

# granite-8b attention widths: 8 KV heads x 4 queries each, head_dim 128
B, S, KV, G, D = 1, 4096, 8, 4, 128
BQ, BK = 512, 1024


@pytest.fixture(scope="module")
def v5e_chips():
    """The four devices of a described (not attached) v5e 2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without that chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_chips):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_chips[0])


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, True, 0, BQ, BK, False)


def _flash_bwd(q, k, v):
    def loss(q, k, v):
        return jnp.sum(_flash_fwd(q, k, v).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


i32, u32, f32, bf16, i8 = (jnp.int32, jnp.uint32, jnp.float32, jnp.bfloat16,
                           jnp.int8)
ROWS = 32768

CASES = {
    "flash_fwd": (_flash_fwd, [((B, S, KV, G, D), bf16), ((B, S, KV, D), bf16),
                               ((B, S, KV, D), bf16)]),
    "flash_bwd": (_flash_bwd, [((B, S, KV, G, D), bf16), ((B, S, KV, D), bf16),
                               ((B, S, KV, D), bf16)]),
    "jacobi_chunked": (functools.partial(jacobi_mars.jacobi_chunked,
                                         t_steps=8, width=512),
                       [((1 << 26,), f32)]),
    "pack": (functools.partial(bitplane.pack, bits=8, block=1024),
             [((ROWS, 1024), i32)]),
    "unpack": (functools.partial(bitplane.unpack, bits=8, block=1024),
               [((ROWS, 1024 // 32 * 8), u32)]),
    "kv_quant8": (functools.partial(kvpack.kv_quant, bits=8),
                  [((ROWS, D), bf16)]),
    "kv_quant4": (functools.partial(kvpack.kv_quant, bits=4),
                  [((ROWS, D), bf16)]),
    "kv_dequant8": (functools.partial(kvpack.kv_dequant, bits=8),
                    [((ROWS, D), i8), ((ROWS, 1), f32)]),
    "kv_dequant4": (functools.partial(kvpack.kv_dequant, bits=4),
                    [((ROWS, D // 2), i8), ((ROWS, 1), f32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_jacobi_pass_has_no_relayout(one_chip):
    """The kernel's (cells/128, 128) view is a bitcast of the 1-D field's
    layout: the compiled pass is the pad fusion, the kernel and the slice,
    with no copy or reshape around the kernel."""
    from repro.kernels import ops
    x = jax.ShapeDtypeStruct((1 << 26,), f32, sharding=one_chip)
    text = jax.jit(functools.partial(
        ops._jacobi1d_tiled_jit, t_steps=8, width=512,
        use_pallas="pallas")).lower(x).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    assert entry.count("tpu_custom_call") == 1
    assert "copy(" not in entry and "reshape(" not in entry, entry


def test_sharded_train_step_compiles_for_v5e_2x2(v5e_chips, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the flash kernel
    must run per shard.  Small widths; the mesh is the four described chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import base
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import layers, model_zoo
    from repro.train import step as ts

    monkeypatch.setattr(layers, "_flash_mode", lambda: True)
    mesh = make_mesh((2, 2), ("data", "model"), devices=v5e_chips)
    cfg = base.ModelConfig(name="flash-sharded", family="dense", n_layers=2,
                           d_model=512, n_heads=8, n_kv_heads=2, head_dim=128,
                           d_ff=1024, vocab=1024)
    rc = base.RunConfig(seq_len=512, global_batch=4, kind="train",
                        q_block=256, kv_block=256)
    with shd.use_rules(shd.Rules(mesh=mesh)):
        api = model_zoo.get_api(cfg, rc)
        abstract = ts.abstract_state(api, rc, mesh)
        specs = ts.resolve_state_specs(ts.state_logical_specs(api, rc, mesh),
                                       abstract)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, sh)
        rep = NamedSharding(mesh, P())
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
                 for k, v in model_zoo.input_specs(cfg, rc).items()}
        step = ts.make_train_step(api, cfg, rc, mesh)
        compiled = jax.jit(step, in_shardings=(sh, None),
                           out_shardings=(sh, None)).lower(state,
                                                           batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_split_kv_heads_compiles_for_v5e_1x4(v5e_chips):
    """2 KV heads on a model axis of 4: each shard slices the KV head its
    two query heads share, forward and backward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import layers

    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_chips)
    heads = NamedSharding(mesh, P(None, None, "model", None))
    whole = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((1, 1024, 8, D), bf16, sharding=heads)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, D), bf16, sharding=whole)

    def loss(q, k, v):
        o = layers._flash_per_shard(q, k, v, causal=True, window=0, bq=512,
                                    bk=512, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    with shd.use_rules(shd.Rules(mesh=mesh)):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kv_cache_write_is_not_a_loop(one_chip):
    """The decode step at granite-8b widths (2 layers, batch 256, an int8
    cache of 576): no ``while`` loop carries the ``kv_cache_write`` scope,
    and a scatter does, so ``kv_write_ms.serve`` has ops to read.  A
    ``vmap`` of ``dynamic_update_slice`` compiles to one serial loop over
    the sequences per cache array."""
    import dataclasses
    import os
    import re
    import sys

    from repro.configs import base
    from repro.configs.granite_8b import CONFIG
    from repro.models import model_zoo

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "..", "benchmarks", "tpu"))
    import devtrace

    batch = 256
    cfg = dataclasses.replace(CONFIG, n_layers=2, rope_theta=1e7)
    rc = base.RunConfig(seq_len=576, global_batch=batch, kind="decode",
                        kv_cache_bits=8)
    api = model_zoo.get_api(cfg, rc)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: api.init_decode_state(batch)))
    tokens = jax.ShapeDtypeStruct((batch,), i32, sharding=one_chip)
    text = jax.jit(api.decode_step).lower(params, state,
                                          tokens).compile().as_text()

    lines = {m.group(1): line for line in text.splitlines()
             if (m := re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line))}
    scoped = [lines[n] for n in devtrace.scoped_instructions(
        text, "kv_cache_write")]
    assert not [line for line in scoped if " while(" in line], scoped
    # the scatters are fusions (in place, aliasing the cache) named for the
    # scatter they hold
    assert any(re.search(r'op_name="[^"]*kv_cache_write/scatter"', line)
               for line in scoped), scoped
