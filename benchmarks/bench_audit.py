"""Beyond-paper: HLO-vs-analytic audit as a gated bench section.

Runs ``python -m repro.launch.audit`` in a subprocess (the audit's wire
program needs a 2-pod mesh, so the child forces
``--xla_force_host_platform_device_count`` before importing jax; the bench
process itself stays single-device).  The audit is host-only by design, so
the child runs with ``JAX_PLATFORMS=cpu``: on a machine with a chip, the
parent process holds it.  It covers the same (size, bits) grid
``bench_collectives`` exchanges, then publishes the report as ``audit/*``
series via ``repro.launch.audit.publish_report`` so the regression gate
fails CI when the compiled HLO drifts from the analytic byte models.
"""
import json
import os
import subprocess
import sys
import tempfile

from repro.launch import audit

from benchmarks.bench_collectives import BITS, SIZES, SMOKE_BITS, SMOKE_SIZES


def run(smoke: bool = False):
    sizes = SMOKE_SIZES if smoke else SIZES[:2]
    bits_grid = SMOKE_BITS if smoke else BITS[:3]
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "audit.json")
        cmd = [sys.executable, "-m", "repro.launch.audit", "--json", out,
               "--sizes", *map(str, sizes), "--bits", *map(str, bits_grid)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(cmd, capture_output=True, text=True, env=env)
        sys.stdout.write(r.stdout)
        if r.returncode or not os.path.exists(out):
            sys.stderr.write(r.stderr)
            raise RuntimeError(
                f"audit subprocess failed (exit {r.returncode})")
        with open(out) as f:
            report = json.load(f)
    audit.publish_report(report)
    print(f"audit: {report['n_checks']} checks, "
          f"{report['divergences']} divergence(s)")


if __name__ == "__main__":
    run()
