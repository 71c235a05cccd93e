"""HLO-vs-analytic audit: prove the byte/FLOP models against compiled XLA.

The repo carries three *analytic* bandwidth models that the paper's claims
rest on:

  * ``distributed/collectives.ExchangeStats`` — wire bytes of the
    compressed cross-pod gradient exchange (planes + scale markers),
  * ``kernels/ops.py`` ``*_io_bytes`` — per-kernel HBM traffic ("read
    every input once, write every output once"),
  * ``launch/roofline.py`` — collective bytes parsed per instruction.

This module is the enforcement that those models describe what XLA
actually compiles.  Each audit lowers a small canonical program, walks the
optimized HLO with ``launch/hlo_walk.py`` (execution-count, replica-group
and dtype aware), and compares the HLO-derived number against the analytic
one:

  * **wire**: the gradient-exchange program (quantize pod-locally,
    all-gather planes+scales across 'pod', pmean raw leaves) compiled on a
    2-pod mesh.  With group size 2 the ring-schedule wire bytes of the
    compiled collectives equal ``ExchangeStats.wire_bytes`` *exactly* —
    an all-gather moves (g-1) one-pod buffers and an all-reduce
    2(g-1)/g of the leaf, both == the analytic charge at g=2.
  * **parsers**: on the same module, ``roofline.collective_bytes`` (the
    independent line parser) must agree with ``analyze_hlo``'s
    per-collective totals (loop-free module -> exact).
  * **kernel IO**: each jitted ref kernel's ENTRY parameter/result bytes
    must equal the ``ops.*_io_bytes`` charge.
  * **flops**: a scan-of-matmul program's walked FLOPs must match the
    trip-count-aware analytic count (tolerance for XLA fusion slack).

``python -m repro.launch.audit`` prints the divergence report and exits
nonzero when any check diverges; ``--perturb-analytic X`` multiplies the
analytic side (CI self-test that the gate actually fires).  The bench
section ``benchmarks/bench_audit.py`` publishes the report as ``audit/*``
series so ``repro.obs.regress`` gates drift per PR.

Byte comparisons are exact (relative tolerance 1e-9 — float round-off
only); FLOPs get a 25% band (fusion/padding slack).  Conventions are
documented in ``src/repro/obs/README.md``.
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # pragma: no cover - CLI needs a multi-dev host
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import argparse
import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple

#: exact tolerance for byte checks (float round-off only)
BYTES_RTOL = 1e-9
#: FLOP checks allow fusion/padding slack
FLOPS_RTOL = 0.25

#: default audit grid — mirrors benchmarks/bench_collectives.py smoke
SIZES = [1 << 16]
BITS = [4, 8]

N_PODS = 2


@dataclasses.dataclass
class AuditCheck:
    """One HLO-derived vs analytic comparison."""
    name: str
    hlo_value: float
    analytic_value: float
    rel_tol: float = BYTES_RTOL
    unit: str = "bytes"
    detail: str = ""

    @property
    def rel_error(self) -> float:
        ref = max(abs(self.analytic_value), 1.0)
        return abs(self.hlo_value - self.analytic_value) / ref

    @property
    def diverged(self) -> bool:
        return self.rel_error > self.rel_tol

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["rel_error"] = self.rel_error
        d["diverged"] = self.diverged
        return d


def summarize(checks: List[AuditCheck],
              programs: Optional[List[dict]] = None) -> dict:
    """Report dict (JSON-serializable) for a list of checks."""
    return {
        "checks": [c.to_dict() for c in checks],
        "programs": programs or [],
        "n_checks": len(checks),
        "divergences": sum(c.diverged for c in checks),
        "ok": not any(c.diverged for c in checks),
    }


def perturb_analytic(checks: List[AuditCheck], factor: float) -> List[AuditCheck]:
    """Scale the analytic side of every check (gate self-test)."""
    return [dataclasses.replace(c, analytic_value=c.analytic_value * factor)
            for c in checks]


def render_report(report: dict) -> str:
    from repro.launch.report import md_table
    rows = []
    for c in report["checks"]:
        rows.append((c["name"], c["unit"],
                     f"{c['hlo_value']:.6g}", f"{c['analytic_value']:.6g}",
                     f"{c['rel_error']:.2e}",
                     "DIVERGED" if c["diverged"] else "ok"))
    table = md_table(("check", "unit", "hlo", "analytic", "rel_err",
                      "status"), rows)
    tail = (f"\n{report['n_checks']} checks — "
            f"{report['divergences']} divergence(s)")
    return "# HLO-vs-analytic audit\n\n" + table + tail


def publish_report(report: dict) -> None:
    """Emit ``audit/*`` series (no-op when obs is disabled).

    ``audit/hlo_<unit>``/``audit/analytic_<unit>`` are deterministic
    functions of the pinned XLA version and the analytic models, so the
    regression gate compares them exactly; ``audit/divergences`` must stay
    at its baseline of 0.
    """
    from repro.obs import instrument as obs
    if not obs.enabled():
        return
    obs.counter_inc("audit/checks", report["n_checks"])
    obs.counter_inc("audit/divergences", report["divergences"])
    for c in report["checks"]:
        obs.gauge_set(f"audit/hlo_{c['unit']}", c["hlo_value"],
                      check=c["name"])
        obs.gauge_set(f"audit/analytic_{c['unit']}", c["analytic_value"],
                      check=c["name"])
        obs.gauge_set("audit/rel_error", c["rel_error"], check=c["name"])


# ---------------------------------------------------------------------------
# Canonical programs (lazy jax imports — the pure half above stays
# importable without initializing a backend)
# ---------------------------------------------------------------------------

def _grad_tree_abstract(n: int):
    """Abstract mirror of benchmarks/bench_collectives._grad_tree."""
    import jax
    import jax.numpy as jnp
    return {
        "w": jax.ShapeDtypeStruct((n // 128, 128), jnp.float32),
        "norm_scale": jax.ShapeDtypeStruct((7,), jnp.float32),
    }


def _exchange_hlo(tree_abs, bits: int) -> str:
    """Compile the canonical cross-pod exchange; return optimized HLO.

    Full-manual ``shard_map`` over a pod-only mesh (no auto axes, no while
    ops — the partial-auto + while combination aborts this XLA's SPMD
    partitioner): each pod quantizes its own full-size gradient, all-gathers
    planes+scales across 'pod', and dequant-averages; raw-fallback leaves
    cross via ``lax.pmean``.  Dequant is applied per gathered pod slice so
    the gather cannot be reassociated into an all-reduce.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((N_PODS,), ("pod",))
    leaves, _ = jax.tree.flatten(tree_abs)
    comp = [collectives.compressible(l) for l in leaves]

    def body(*locals_):
        outs = []
        for x1, is_c in zip(locals_, comp):
            x = x1[0]
            if not is_c:
                outs.append(jax.lax.pmean(x, "pod"))
                continue
            planes, scale = collectives._quant_lastdim(x, bits)
            gp = jax.lax.all_gather(planes, "pod")
            gs = jax.lax.all_gather(scale, "pod")
            total = None
            for i in range(N_PODS):
                d = collectives._dequant_lastdim(gp[i], gs[i], bits, x.shape)
                total = d if total is None else total + d
            outs.append(total / N_PODS)
        return tuple(outs)

    sm = jax.shard_map(
        body, mesh=mesh, axis_names=frozenset({"pod"}),
        in_specs=tuple(P("pod") for _ in leaves),
        out_specs=tuple(P() for _ in leaves), check_vma=False)
    args = [jax.ShapeDtypeStruct((N_PODS,) + l.shape, l.dtype)
            for l in leaves]
    return jax.jit(sm).lower(*args).compile().as_text()


def wire_audit(n: int, bits: int) -> Tuple[List[AuditCheck], dict]:
    """Exchange wire bytes: walked HLO collectives vs ``ExchangeStats``."""
    from repro.distributed import collectives
    from repro.launch import hlo_walk, roofline

    tree_abs = _grad_tree_abstract(n)
    stats = collectives.exchange_stats(tree_abs, bits)
    hlo = _exchange_hlo(tree_abs, bits)
    walk = hlo_walk.analyze_hlo(hlo)

    hlo_wire = sum(d.wire_bytes for d in walk["collective_details"])
    checks = [AuditCheck(
        name=f"wire/n{n}/bits{bits}",
        hlo_value=hlo_wire, analytic_value=float(stats.wire_bytes),
        detail=f"{len(walk['collective_details'])} collectives; "
               f"{stats.compressed_leaves} compressed + "
               f"{stats.raw_leaves} raw leaves")]

    # independent parser agreement: roofline's per-line collective_bytes
    # vs the walker's per-collective max(result, operand) totals
    rl_total = float(sum(roofline.collective_bytes(hlo).values()))
    walk_total = float(sum(walk["collectives"].values()))
    checks.append(AuditCheck(
        name=f"parsers/n{n}/bits{bits}",
        hlo_value=walk_total, analytic_value=rl_total,
        detail="hlo_walk vs roofline collective parsers"))

    prog = {"name": f"exchange/n{n}/bits{bits}",
            "dma_bytes": walk["dma_bytes"],
            "flops": walk["flops"],
            "collectives": walk["collective_wire_bytes"],
            "n_collectives": len(walk["collective_details"])}
    return checks, prog


def kernel_io_audit() -> List[AuditCheck]:
    """ENTRY parameter/result bytes of jitted ref kernels vs ``ops``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.launch import hlo_walk

    n, block = 256, 32
    rows, d = 64, 64
    jn = 4096
    t_steps = 4

    def lower(fn, *specs):
        return jax.jit(fn).lower(*specs).compile().as_text()

    s = jax.ShapeDtypeStruct
    cases = []
    for bits in (4, 8):
        cases.append((
            f"kernel/pack/bits{bits}",
            lower(lambda q, b=bits: ref.pack_ref(q, b),
                  s((n, block), jnp.int32)),
            ops.pack_io_bytes(n, block, bits)))
        cases.append((
            f"kernel/unpack/bits{bits}",
            lower(lambda p, b=bits: ref.unpack_ref(p, b, block),
                  s((n, block // 32 * bits), jnp.uint32)),
            ops.unpack_io_bytes(n, block, bits)))
        cases.append((
            f"kernel/kv_quant/bits{bits}",
            lower(lambda x, b=bits: ref.kv_quant_ref(x, b),
                  s((rows, d), jnp.float32)),
            ops.kv_quant_io_bytes(rows, d, bits)))
        cd = d if bits == 8 else d // 2
        cases.append((
            f"kernel/kv_dequant/bits{bits}",
            lower(lambda c, sc, b=bits: ref.kv_dequant_ref(c, sc, b),
                  s((rows, cd), jnp.int8), s((rows,), jnp.float32)),
            ops.kv_dequant_io_bytes(rows, d, bits)))
    cases.append((
        "kernel/jacobi1d",
        lower(lambda x: ref.jacobi_chunked_ref(x, t_steps),
              s((jn,), jnp.float32)),
        ops.jacobi_io_bytes(jn)))

    checks = []
    for name, hlo, (want_r, want_w) in cases:
        got_r, got_w = hlo_walk.entry_io_bytes(hlo)
        checks.append(AuditCheck(name=f"{name}/read",
                                 hlo_value=float(got_r),
                                 analytic_value=float(want_r)))
        checks.append(AuditCheck(name=f"{name}/write",
                                 hlo_value=float(got_w),
                                 analytic_value=float(want_w)))
    return checks


def flops_audit() -> AuditCheck:
    """Trip-count-aware walked FLOPs of a scan-of-matmul vs analytic."""
    import jax
    import jax.numpy as jnp

    from repro.launch import hlo_walk

    n, k = 128, 10

    def f(x, w):
        def step(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(step, x, None, length=k)
        return y

    s = jax.ShapeDtypeStruct((n, n), jnp.float32)
    hlo = jax.jit(f).lower(s, s).compile().as_text()
    walked = hlo_walk.analyze_hlo(hlo)["flops"]
    return AuditCheck(name=f"flops/scan_matmul/n{n}/k{k}",
                      hlo_value=float(walked),
                      analytic_value=float(k * 2 * n ** 3),
                      rel_tol=FLOPS_RTOL, unit="flops",
                      detail="while trip count x dot contracting dims")


def build_report(sizes: List[int], bits_grid: List[int],
                 perturb: float = 1.0) -> dict:
    import jax
    checks: List[AuditCheck] = []
    programs: List[dict] = []
    if len(jax.devices()) >= N_PODS:
        for n in sizes:
            for bits in bits_grid:
                cs, prog = wire_audit(n, bits)
                checks.extend(cs)
                programs.append(prog)
    else:  # pragma: no cover - defensive: wire audit needs a 2-pod mesh
        programs.append({"name": "exchange", "skipped":
                         f"only {len(jax.devices())} device(s)"})
    checks.extend(kernel_io_audit())
    checks.append(flops_audit())
    if perturb != 1.0:
        checks = perturb_analytic(checks, perturb)
    return summarize(checks, programs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Cross-validate compiled-HLO bytes/FLOPs against the "
                    "analytic collective/kernel/roofline models.")
    ap.add_argument("--sizes", type=int, nargs="+", default=SIZES)
    ap.add_argument("--bits", type=int, nargs="+", default=BITS)
    ap.add_argument("--json", help="also write the report as JSON")
    ap.add_argument("--perturb-analytic", type=float, default=1.0,
                    help="multiply analytic values (self-test: any value "
                         "!= 1.0 must make the audit exit nonzero)")
    args = ap.parse_args(argv)

    report = build_report(args.sizes, args.bits,
                          perturb=args.perturb_analytic)
    print(render_report(report))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {args.json}")
    if not report["ok"]:
        print("\nFAIL: HLO-derived traffic diverged from the analytic "
              "model — fix the model (or hlo_walk) before trusting the "
              "roofline/bandwidth numbers.")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
