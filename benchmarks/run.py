"""Benchmark aggregator: one section per paper table/figure + beyond-paper.

``python -m benchmarks.run [--smoke] [--out benchmarks/out] [--seed 0]``

Every run is reproducible and attributable:

* all RNG is seeded explicitly (``--seed`` feeds ``numpy`` global state and
  ``random``; the sections themselves use fixed ``default_rng`` seeds);
* ``<out>/BENCH.json`` records per-section status/duration plus run
  metadata — git SHA, dirty flag, config name, seed, argv;
* ``<out>/BENCH_obs.json`` is the observability sidecar
  (``repro.obs.sink.write_sidecar``): every ``transfer/cycles``,
  ``compression/ratio``, ... series the sections emitted, renderable with
  ``python -m repro.obs.report <out>``.

``--smoke`` is the CI-safe mode: every section runs with reduced case
grids (the beyond-paper benches shrink their sweeps and use the jnp ``ref``
kernel backend), a few seconds end to end — small enough for CI, complete
enough that ``python -m repro.obs.regress`` can gate the kernels /
collectives / ckpt series every PR.
"""
import argparse
import json
import os
import random
import sys
import time

import numpy as np

from repro import obs
from repro.launch.compile_cache import enable_compile_cache

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out")


def sections(smoke: bool):
    from benchmarks import (bench_analysis, bench_ckpt, bench_codec,
                            bench_collectives, bench_stencil_kernel,
                            fig10_transfer, fig11_ratio, table1_mars,
                            table2_compile)

    # every section runs in smoke mode too (reduced grids) so the
    # regression gate sees kernels/collectives/ckpt series in CI
    return [
        ("table1_mars", "Table 1 — MARS & burst counts", table1_mars.run),
        ("table2_compile", "Table 2 — layout + analysis time",
         table2_compile.run),
        ("fig10_transfer", "Fig 10 — transfer cycles by access pattern",
         lambda: fig10_transfer.run(smoke=smoke)),
        ("fig11_ratio", "Fig 11 — compression ratio vs dtype x tile",
         lambda: fig11_ratio.run(smoke=smoke)),
        ("bench_codec", "Beyond-paper: vectorized codec + executor",
         lambda: bench_codec.run(smoke=smoke)),
        ("bench_collectives", "Beyond-paper: compressed collectives",
         lambda: bench_collectives.run(smoke=smoke)),
        ("bench_stencil_kernel",
         "Beyond-paper: irredundant stencil kernel",
         lambda: bench_stencil_kernel.run(smoke=smoke)),
        ("bench_ckpt", "Beyond-paper: checkpoint save/restore",
         lambda: bench_ckpt.run(smoke=smoke)),
        ("bench_analysis", "Beyond-paper: static layout/access linter",
         bench_analysis.run),
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI-safe subset (paper sections, small grids)")
    ap.add_argument("--quick", action="store_true",
                    help="deprecated alias for --smoke")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="directory for BENCH.json + BENCH_obs.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    smoke = args.smoke or args.quick
    enable_compile_cache()

    # explicit global seeding: sections use their own default_rng(0)
    # streams, but anything reaching numpy/python global state is pinned too
    np.random.seed(args.seed)
    random.seed(args.seed)

    config_name = "smoke" if smoke else "full"
    meta = obs.run_metadata(config=config_name, seed=args.seed, smoke=smoke)

    obs.enable(obs.Registry(), obs.Tracer())
    results = []
    failures = []
    for key, title, fn in sections(smoke):
        print(f"\n=== {title} ===")
        t0 = time.time()
        try:
            with obs.span(f"bench/{key}"):
                fn()
            dt = time.time() - t0
            results.append({"section": key, "ok": True, "seconds": dt})
            print(f"[ok in {dt:.1f}s]")
        except Exception as e:  # pragma: no cover
            results.append({"section": key, "ok": False, "seconds":
                            time.time() - t0, "error": repr(e)})
            failures.append((title, e))
            print(f"[FAILED: {e}]")

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "BENCH.json"), "w") as f:
        json.dump({"meta": meta, "sections": results}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    sidecar = obs.write_sidecar(args.out, meta=meta)
    obs.write_jsonl(os.path.join(args.out, "obs.jsonl"), meta=meta)
    obs.disable()
    print(f"\nwrote {sidecar} "
          f"(render: python -m repro.obs.report {args.out})")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
