#!/usr/bin/env python3
"""Readings for the MoE serve cell's limits of ``correct``: sound runs and
controls, many seeds in one process (a chip run, as ``calibrate.py``).

    python3 benchmarks/tpu/calibrate_moe.py --workload <cell> \
        --seeds 1,2,3 [--control capacity|top1|int4] [--units 1] \
        [--check-requests 64]

Each seed prints one JSON line: the numbers compared, and ``sweep``, the
largest gap and the near-tie count under other router margins and over
the first 4, 8, 16, ... requests compared, from which the cell's margin,
requests and limits are chosen.

Controls, each in the program's place under the timed path:

- ``capacity``: a capacity-dropping expert layer planted in place of
  ``models/moe.moe_block``: GShard/Switch dispatch, each expert takes at
  most int(factor * k * tokens / E) rows, in the order of the flattened
  (token, choice) rows, and drops the rest (factor 1.25, as GShard and
  Switch deploy it);
- ``top1``: the program's dropless layer routing each token to its top
  expert alone;
- ``int4``: the program's int4 KV cache, the next precision below the int8
  cache the cell states.
"""
import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

CAPACITY_FACTOR = 1.25
MARGINS = (0.0, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5)


def capacity_moe_block(x, p, cfg, group0=0,
                       factor: float = CAPACITY_FACTOR):
    """The capacity-dropping dispatch: (B, S, d) -> (out, aux); ``p`` as
    ``models/moe.moe_block`` takes it, the layer's experts the groups of
    the table from ``group0``."""
    import jax
    import jax.numpy as jnp
    E = cfg.n_experts
    p = p._replace(**{k: jax.lax.dynamic_slice_in_dim(getattr(p, k), group0,
                                                      E)
                      for k in ("w_gate", "w_up", "w_down")})
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.topk
    n = B * S
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(jnp.dot(xf, p.router,
                                   preferred_element_type=jnp.float32), -1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    cap = max(int(factor * k * n / E), 1)
    e_flat = top_e.reshape(-1)                            # (n*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                              e_flat[:, None], axis=1)[:, 0]
    keep = pos < cap
    pos = jnp.where(keep, pos, cap)                       # cap: dropped
    tok = jnp.repeat(jnp.arange(n), k)
    buf = jnp.zeros((E, cap, d), x.dtype).at[e_flat, pos].add(
        jnp.take(xf, tok, axis=0), mode="drop")           # (E, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p.w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p.w_up)
    y = jnp.einsum("ecf,efd->ecd", h, p.w_down)
    got = y.at[e_flat, pos].get(mode="fill", fill_value=0)
    got = jnp.where(keep[:, None], got, 0) * top_w.reshape(-1)[:, None] \
        .astype(x.dtype)
    out = jnp.zeros((n, d), x.dtype).at[tok].add(got)
    return out.reshape(B, S, d), jnp.zeros((), jnp.float32)


CONTROLS = ("capacity", "top1", "int4")
DRIVER_KW = {"int4": {"kv_cache_bits": 4}}


@contextlib.contextmanager
def planted(control: str):
    """The program with ``control``'s expert layer in place of its own
    (``transformer`` looks ``moe.moe_block`` up when it traces a step)."""
    from repro.models import moe
    dropless = moe.moe_block
    layers = {"capacity": capacity_moe_block,
              "top1": lambda x, p, cfg, group0=0: dropless(
                  x, p, dataclasses.replace(cfg, topk=1), group0)}
    if control not in layers:
        yield
        return
    moe.moe_block = layers[control]
    try:
        yield
    finally:
        moe.moe_block = dropless


def sweep(gaps, margins):
    """The largest gap and the near-tie count under other router margins
    and over the first 4, 8, 16, ... sampled requests (the sample's
    order), to choose the margin and the number of requests compared."""
    out = {}
    n = 4
    while n <= len(gaps):
        for m in MARGINS:
            tie = margins[:n] < m
            clear = gaps[:n][~tie]
            out[f"{n}r/{m}"] = [float(clear.max()) if clear.size else 0.0,
                                int(tie.sum())]
        n *= 2
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=1)
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--check-requests", type=int, default=0,
                    help="requests compared (default: the traffic's)")
    args = ap.parse_args(argv)
    try:
        spec = run.load_cell(args.workload)
    except run.NoChip as e:
        print(f"calibrate_moe.py: {e}", file=sys.stderr)
        return 2
    traffic = spec["traffic"]
    if args.check_requests:
        traffic = dict(traffic, check_requests=args.check_requests)
    driver = run.driver_class(traffic)
    extra = DRIVER_KW.get(args.control, {})
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        with planted(args.control):
            d = driver(spec["config"], traffic, seed, spec["devices"], **extra)
            d.setup()
            for _ in range(args.units):
                d.unit()
        checks = d.check()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "seconds": time.perf_counter() - t0,
                          "checks": {c[0]: c[1] for c in checks},
                          "sweep": sweep(d.gaps, d.margins)}), flush=True)
        del d
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
