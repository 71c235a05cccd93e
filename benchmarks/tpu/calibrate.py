#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, many seeds in one
process (a chip run; the benchmark's own runs never do this).

    python3 benchmarks/tpu/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--units 1] [--control]

For each seed: the cell's set-up, ``--units`` units of its window, and its
check; one JSON line of the numbers compared.  ``--control`` runs the
cell's control in the program's place: the serve cell with the program's
own int4 KV cache (the next precision below the int8 it states), the
stencil with the reference computed in bfloat16, the train cell with the
program's bfloat16 parameters (its path below the f32 the configuration
states).  The cell is loaded as ``run.py`` loads it (``run.load_cell``).
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

CONTROLS = {"serve": {"kv_cache_bits": 4}, "stencil": {"control": True},
            "train": {"param_dtype": "bfloat16"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=1)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        spec = run.load_cell(args.workload)
    except run.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    traffic = spec["traffic"]
    driver = run.driver_class(traffic)
    extra = CONTROLS[traffic["driver"]] if args.control else {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        d = driver(spec["config"], traffic, seed, spec["devices"], **extra)
        d.setup()
        for _ in range(args.units):
            d.unit()
        if hasattr(d, "drain"):
            d.drain()
        checks = d.check()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "seconds": time.perf_counter() - t0,
                          "checks": {c[0]: c[1] for c in checks}}), flush=True)
        del d
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
