"""The yardstick's counts against numbers worked out by hand."""
import json
import os

import pytest

import counts
import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = peaks.lookup("TPU v5 lite")


def config(name, **changes):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        c = json.load(f)
    c.update(changes)
    return c


def test_granite_matmul_params():
    c = config("granite-8b-L8", num_hidden_layers=1)
    # q 4096x4096, k and v 4096x1024, o 4096x4096, SwiGLU 3 x 4096x14336
    assert counts.layer_matmul_params(c) == 218_103_808
    # LM head 4096 x 49152; the input embedding is a lookup
    assert counts.matmul_params(c) == 218_103_808 + 201_326_592


def test_train_step_flops_granite_l3():
    """4.45e13 FLOP per step of 3 layers at 2 x 4096 tokens."""
    c = config("granite-8b-L8", num_hidden_layers=3)
    f = counts.train_step_flops(c, batch=2, seq=4096)
    assert f == pytest.approx(6 * 855_638_016 * 8192
                              + 6 * 4096 ** 2 * 4096 * 2 * 3)
    assert f == pytest.approx(4.45e13, rel=1e-3)


def test_serve_step_byte_bound():
    """7.46 ms: 3.9 GB of weights and the whole 2.2 GB int8 cache."""
    c = config("granite-8b-L8")
    b = counts.decode_step_bytes(c, batch=32, ctx=4095, kv_bits=8)
    weights = 2 * (8 * 218_103_808 + 201_326_592)
    cache = 32 * 4096 * 8 * 8 * 2 * (128 + 4)
    assert b == pytest.approx(weights + 32 * 4096 * 2 + cache)
    assert b / V5E.hbm_bytes * 1e3 == pytest.approx(7.46, abs=0.01)
    # the bf16 cache holds twice the codes and no scales
    assert counts.kv_row_bytes(128, 16) == 256
    assert counts.kv_row_bytes(128, 4) == 68


def test_decode_step_is_bound_by_bytes():
    c = config("granite-8b-L8")
    f = counts.decode_step_flops(c, 32, 512)
    b = counts.decode_step_bytes(c, 32, 512, 8)
    assert counts.least_seconds(f, b, V5E) == b / V5E.hbm_bytes


def test_stencil_pass_byte_bound():
    """5.2 ms per pass over 2^29 f32 cells, each read and written once."""
    n = config("jacobi-1d-2e29")["cells"]
    assert n == 2 ** 29
    assert counts.jacobi_pass_bytes(n) / V5E.hbm_bytes * 1e3 == \
        pytest.approx(5.24, abs=0.01)


def test_flash_counts_granite_heads():
    # 32 heads of 128, 8 KV heads, one 4096-token sequence
    f, b = counts.flash_fwd(1, 4096, 32, 8, 128)
    assert f == pytest.approx(4 * (4096 * 4097 / 2) * 128 * 32)
    assert b == 2 * 4096 * 32 * 128 * 2 + 2 * 4096 * 8 * 128 * 2 + 32 * 4096 * 4
    fkv, _ = counts.flash_bwd_dkv(1, 4096, 32, 8, 128)
    fq, _ = counts.flash_bwd_dq(1, 4096, 32, 8, 128)
    assert (fkv / f, fq / f) == (2.0, 1.5)
    # compute binds all three at these shapes
    for fl, by in (counts.flash_fwd(1, 4096, 32, 8, 128),
                   counts.flash_bwd_dq(1, 4096, 32, 8, 128)):
        assert fl / V5E.flops > by / V5E.hbm_bytes


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError):
        peaks.lookup("cpu")
