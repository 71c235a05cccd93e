"""Multi-pass orchestration for ``repro.analysis``.

``run_all`` executes the three pass families, applies the suppression
baseline, and returns a JSON-serializable report dict.  ``publish_report``
emits ``analysis/*`` series so ``repro.obs.regress`` gates finding counts
per PR, and ``selftest`` injects one violation per rule family and
verifies each pass actually fires.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from . import access, layout_invariants, obs_discipline
from .findings import (Finding, load_baseline, sort_findings,
                       split_by_baseline)

#: source tree the obs-discipline pass walks by default
DEFAULT_ROOT = "src/repro"


def run_all(root: str = DEFAULT_ROOT,
            baseline_path: Optional[str] = None) -> dict:
    """Run every pass; split findings against the suppression baseline."""
    per_pass: Dict[str, List[Finding]] = {
        access.PASS_NAME: access.run_pass(),
        obs_discipline.PASS_NAME: obs_discipline.analyze_tree(root),
        layout_invariants.PASS_NAME: layout_invariants.run_pass(),
    }

    findings = sort_findings(
        [f for fs in per_pass.values() for f in fs])
    baseline = load_baseline(baseline_path) if baseline_path else (
        load_baseline())
    new, suppressed = split_by_baseline(findings, baseline)
    return {
        "findings": [f.to_dict() for f in findings],
        "new": [f.to_dict() for f in new],
        "suppressed": [f.to_dict() for f in suppressed],
        "per_pass": {k: len(v) for k, v in per_pass.items()},
        "n_findings": len(findings),
        "n_new": len(new),
        "n_suppressed": len(suppressed),
    }


def worst_new_severity(report: dict) -> Optional[str]:
    sevs = [f["severity"] for f in report["new"]]
    for s in ("error", "warning", "info"):
        if s in sevs:
            return s
    return None


def render_report(report: dict) -> str:
    lines = []
    for f in report["new"]:
        lines.append(f"{f['severity'].upper():7s} {f['rule']} "
                     f"{f['location']}: {f['message']}")
    for f in report["suppressed"]:
        lines.append(f"suppressed {f['rule']} {f['location']} "
                     f"[{f['fingerprint']}]")
    per = ", ".join(f"{k}={v}" for k, v in sorted(
        report["per_pass"].items()))
    lines.append(f"analysis: {report['n_new']} new, "
                 f"{report['n_suppressed']} suppressed ({per})")
    return "\n".join(lines)


def publish_report(report: dict) -> None:
    """Emit ``analysis/*`` series (no-op when obs is disabled).

    ``analysis/new_findings`` must stay at its baseline of 0 — the
    regression gate compares it exactly, so a PR that introduces a
    violation fails the bench gate even if nobody ran the CLI.
    """
    from repro.obs import instrument as obs
    if not obs.enabled():
        return
    obs.counter_inc("analysis/findings", report["n_findings"])
    obs.counter_inc("analysis/new_findings", report["n_new"])
    obs.counter_inc("analysis/suppressed", report["n_suppressed"])
    for pass_name, n in sorted(report["per_pass"].items()):
        obs.counter_inc("analysis/pass_findings", n, pass_name=pass_name)


# ---------------------------------------------------------------------------
# Selftest: one injected violation per rule family
# ---------------------------------------------------------------------------

#: source fixture: obs recording reachable through a jitted helper
OBS_UNDER_JIT_SRC = """\
import jax
from repro.obs import instrument as obs

def helper(x):
    obs.counter_inc("bad/inside_trace", 1)
    return x

@jax.jit
def kernel(x):
    return helper(x)
"""


def selftest() -> dict:
    """Inject one violation per rule family; report which passes fired.

    Returns ``{"ok": bool, "fired": {injection: bool}}`` — ``ok`` only
    when every injected violation was caught.  This is the proof that a
    green analyzer run means "checked and clean", not "checked nothing".
    """
    from repro.core import layout, mars, stencil

    fired: Dict[str, bool] = {}

    # 1. misaligned pack width (ACC103): 5 bits does not tile 32
    fs = access.check_pack_alignment(
        access.PackCase("selftest/misaligned", bits=5, block=48))
    fired["misaligned-pack"] = sum(f.rule == "ACC103" for f in fs) == 2

    # 2. obs recording under jit (OBS201)
    nodes = obs_discipline.scan_source(OBS_UNDER_JIT_SRC, "selftest_obs.py")
    fs = obs_discipline.run_pass(nodes)
    fired["obs-under-jit"] = any(f.rule == "OBS201" for f in fs)

    # 3. invalid layout permutation (LAY301): duplicate an index
    a = mars.analyze(stencil.SPECS["jacobi-1d"]((6, 6)))
    good = layout.layout_for_analysis(a)
    bad_order = list(good.order)
    bad_order[0] = bad_order[1]
    import dataclasses
    bad = dataclasses.replace(good, order=tuple(bad_order))
    fs = layout_invariants.check_layout("jacobi-1d", (6, 6), a, result=bad)
    fired["invalid-permutation"] = any(f.rule == "LAY301" for f in fs)

    # 4. burst-count lie (LAY302)
    lied = dataclasses.replace(good, read_bursts=good.read_bursts + 1)
    fs = layout_invariants.check_layout("jacobi-1d", (6, 6), a, result=lied)
    fired["burst-miscount"] = any(f.rule == "LAY302" for f in fs)

    return {"ok": all(fired.values()), "fired": fired}
