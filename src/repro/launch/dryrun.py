import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
platform devices stand in for 2 pods x 256 chips.  For every cell the step
function (train_step / prefill / decode_step) is jit'd with explicit
in/out shardings, ``.lower()``ed against ShapeDtypeStruct inputs (no
allocation) and ``.compile()``d; we record

  * cost_analysis()  — per-device FLOPs / bytes for §Roofline,
  * memory_analysis() — per-device argument/output/temp bytes (fits-proof),
  * the collective schedule parsed from the optimized HLO.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all          # orchestrates subprocesses
  python -m repro.launch.dryrun --all --mesh multi
Results land in benchmarks/out/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

#: the chip the dry-run's roofline terms are computed for
TARGET_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "out", "dryrun")


def run_cell(arch: str, shape: str, mesh_kind: str, overrides=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import base
    from repro.distributed import sharding as shd
    from repro.launch import cells, roofline
    from repro.launch.mesh import make_production_mesh
    from repro.models import model_zoo
    from repro.train import step as ts

    t_start = time.time()
    import dataclasses as _dc
    overrides = dict(overrides or {})
    cfg_over = {k[4:]: overrides.pop(k) for k in list(overrides)
                if k.startswith("cfg.")}
    cfg = base.load_arch(arch)
    if cfg_over:
        cfg = _dc.replace(cfg, **cfg_over)
    rc = cells.resolve_run_config(arch, shape, **overrides)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "kind": rc.kind,
           "chips": chips, "ok": False}

    rules = shd.Rules(mesh=mesh, seq_shard=rc.seq_shard, fsdp=rc.fsdp,
                      shard_vocab=rc.shard_vocab)
    with shd.use_rules(rules):
        api = model_zoo.get_api(cfg, rc)
        ns = lambda spec: NamedSharding(mesh, spec)

        params_logical = api.param_specs()
        params_abs = api.abstract_params()
        params_sh = jax.tree.map(ns, shd.spec_tree(params_logical, params_abs))

        batch_abs = model_zoo.input_specs(cfg, rc)
        batch_logical = model_zoo.batch_logical_specs(cfg, rc)
        batch_sh = {k: ns(rules.spec(batch_abs[k].shape, batch_logical[k]))
                    for k in batch_abs}

        if rc.kind == "train":
            step_fn = ts.make_train_step(api, cfg, rc, mesh)
            state_abs = ts.abstract_state(api, rc, mesh)
            state_sh = jax.tree.map(
                ns, ts.resolve_state_specs(
                    ts.state_logical_specs(api, rc, mesh), state_abs))
            jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            args = (state_abs, batch_abs)
        elif rc.kind == "prefill":
            jitted = jax.jit(lambda p, b: api.prefill(p, b),
                             in_shardings=(params_sh, batch_sh))
            args = (params_abs, batch_abs)
        else:  # decode
            state_abs = jax.eval_shape(
                lambda: api.init_decode_state(rc.global_batch))
            state_logical = api.decode_state_specs()
            state_sh = jax.tree.map(ns, shd.spec_tree(state_logical, state_abs))
            tok_sh = batch_sh["tokens"]
            jitted = jax.jit(lambda p, s, t: api.decode_step(p, s, t),
                             in_shardings=(params_sh, state_sh, tok_sh),
                             out_shardings=(None, state_sh),
                             donate_argnums=(1,))
            args = (params_abs, state_abs, batch_abs["tokens"])

        t0 = time.time()
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        # --- analyses -----------------------------------------------------
        # raw XLA numbers (while bodies counted ONCE — kept for reference)
        from repro.launch import hlo_walk
        ca = hlo_walk.cost_analysis_dict(compiled)
        rec["xla_flops_raw"] = float(ca.get("flops", 0.0))
        rec["xla_bytes_raw"] = float(ca.get("bytes accessed", 0.0))

        ma = compiled.memory_analysis()
        if ma is not None:
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes"):
                v = getattr(ma, field, None)
                if v is not None:
                    rec[field] = int(v)
        print("memory_analysis:", ma)

        # trip-count-aware walk of the partitioned module (per-device numbers)
        from repro.launch import hlo_walk
        hlo = compiled.as_text()
        if os.environ.get("REPRO_DUMP_HLO"):
            with open(os.environ["REPRO_DUMP_HLO"], "w") as f:
                f.write(hlo)
        walk = hlo_walk.analyze_hlo(hlo)
        rec["flops_per_device"] = float(walk["flops"])
        rec["bytes_per_device"] = float(walk["traffic_bytes"])
        rec["scoped_traffic"] = walk["scoped_traffic"]
        rec["collectives"] = walk["collectives"]
        rec["hlo_bytes"] = len(hlo)

        # kernelized deployment: scoped interiors (flash-attn / SSD chunk)
        # run as Pallas kernels on TPU — their HBM traffic collapses to I/O
        interior = float(sum(walk["scoped_traffic"].values()))
        kio = roofline.kernelized_io_bytes(cfg, rc, chips)
        rec["bytes_per_device_kernelized"] = max(
            rec["bytes_per_device"] - interior, 0.0) + kio

        rec["model_flops"] = roofline.model_flops_for(cfg, rc)
        rec["target_kind"] = TARGET_KIND
        rl = roofline.analyze(rec["flops_per_device"], rec["bytes_per_device"],
                              rec["collectives"], chips, rec["model_flops"],
                              device_kind=TARGET_KIND)
        rlk = roofline.analyze(rec["flops_per_device"],
                               rec["bytes_per_device_kernelized"],
                               rec["collectives"], chips, rec["model_flops"],
                               device_kind=TARGET_KIND)
        rec["roofline"] = {
            "compute_s": rl.compute_s, "memory_s": rl.memory_s,
            "memory_s_kernelized": rlk.memory_s,
            "collective_s": rl.collective_s, "dominant": rl.dominant,
            "dominant_kernelized": rlk.dominant,
            "model_flops_ratio": rl.model_flops_ratio,
            "mfu_bound": rl.mfu_bound,
            "mfu_bound_kernelized": rlk.mfu_bound,
        }
        print("cost_analysis: flops=%.3e bytes=%.3e" %
              (rec["flops_per_device"], rec["bytes_per_device"]))
        print("collectives:", rec["collectives"])
        print("roofline:", json.dumps(rec["roofline"], indent=1))
        rec["ok"] = True
        rec["total_s"] = round(time.time() - t_start, 2)
    return rec


def cell_path(outdir, arch, shape, mesh_kind, tag=""):
    suffix = f"__{tag}" if tag else ""
    return os.path.join(outdir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--tag", default="", help="experiment tag for §Perf runs")
    ap.add_argument("--override", action="append", default=[],
                    help="RunConfig overrides key=value (e.g. kv_cache_bits=8)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    if args.all:
        from repro.launch import cells
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        todo = [(a, s, m) for (a, s) in cells.runnable_cells() for m in meshes]
        failed = []
        for a, s, m in todo:
            path = cell_path(args.out, a, s, m, args.tag)
            if os.path.exists(path) and not args.force:
                try:
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"skip (done): {a} {s} {m}")
                            continue
                except (json.JSONDecodeError, OSError):
                    pass
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m, "--out", args.out]
            if args.tag:
                cmd += ["--tag", args.tag]
            for kv in args.override:
                cmd += ["--override", kv]
            print(f"=== {a} {s} {m} ===", flush=True)
            r = subprocess.run(cmd, timeout=args.timeout)
            if r.returncode != 0:
                failed.append((a, s, m))
        print("FAILED CELLS:", failed)
        sys.exit(1 if failed else 0)

    assert args.arch and args.shape
    path = cell_path(args.out, args.arch, args.shape, args.mesh, args.tag)
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, overrides)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        print(rec["traceback"], file=sys.stderr)
    if overrides:
        rec["overrides"] = overrides
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {path} ok={rec['ok']}")
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
