"""repro.analysis: rule firing on injected violations + clean-tree green.

Each violation fixture — misaligned pack width, obs-call-under-jit,
invalid layout permutation, burst-count lie — must produce a finding,
and the real tree must be clean.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import access, layout_invariants, obs_discipline, runner
from repro.analysis.findings import (Finding, load_baseline, sort_findings,
                                     split_by_baseline, write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(args, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-m", "repro.analysis", *args],
                          capture_output=True, text=True, env=env, cwd=cwd)


# ---------------------------------------------------------------------------
# findings model
# ---------------------------------------------------------------------------

def test_finding_fingerprint_stable_across_line_drift():
    a = Finding("OBS201", "error", "repro/x.py:10", "msg")
    b = Finding("OBS201", "error", "repro/x.py:99", "msg")
    c = Finding("OBS201", "error", "repro/y.py:10", "msg")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != c.fingerprint


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding("X", "fatal", "loc", "msg")


def test_baseline_roundtrip(tmp_path):
    f1 = Finding("ACC103", "error", "k/a", "m1")
    f2 = Finding("OBS201", "warning", "k/b", "m2")
    path = str(tmp_path / "baseline.json")
    write_baseline([f1], path)
    base = load_baseline(path)
    new, suppressed = split_by_baseline([f1, f2], base)
    assert suppressed == [f1] and new == [f2]
    assert sort_findings([f2, f1])[0] is f1  # error sorts before warning


# ---------------------------------------------------------------------------
# injected-violation fixtures (one per acceptance criterion)
# ---------------------------------------------------------------------------

def test_misaligned_pack_fixture_fires():
    case = access.PackCase("fx/misaligned", bits=5, block=48)
    fs = access.check_pack_alignment(case)
    assert sum(f.rule == "ACC103" for f in fs) == 2  # width + block
    ok = access.PackCase("fx/aligned", bits=4, block=32)
    assert access.check_pack_alignment(ok) == []


def test_obs_under_jit_fixture_fires():
    nodes = obs_discipline.scan_source(runner.OBS_UNDER_JIT_SRC, "fx.py")
    fs = obs_discipline.run_pass(nodes)
    assert len(fs) == 1
    assert fs[0].rule == "OBS201" and fs[0].severity == "error"
    assert "counter_inc" in fs[0].message and "fx.py::kernel" in fs[0].message


def test_obs_host_side_recording_is_clean():
    src = textwrap.dedent("""\
        import jax
        from repro.obs import instrument as obs

        @jax.jit
        def kernel(x):
            return x * 2

        def host(x):
            with obs.span("host/step"):
                obs.counter_inc("host/calls", 1)
                return kernel(x)
    """)
    assert obs_discipline.run_pass(obs_discipline.scan_source(src, "h.py")) \
        == []


def test_obs_pass_catches_scan_body_and_lambda():
    src = textwrap.dedent("""\
        import jax
        from repro.obs import instrument as obs

        def step(carry, x):
            obs.gauge_set("bad/inner", 1.0)
            return carry, x

        def run(xs):
            return jax.lax.scan(step, 0, xs)
    """)
    fs = obs_discipline.run_pass(obs_discipline.scan_source(src, "s.py"))
    assert len(fs) == 1 and "passed to jax.lax.scan" in fs[0].message


def test_invalid_layout_permutation_fixture_fires():
    import dataclasses

    from repro.core import layout, mars, stencil

    a = mars.analyze(stencil.SPECS["jacobi-1d"]((6, 6)))
    good = layout.layout_for_analysis(a)
    bad = dataclasses.replace(
        good, order=tuple([good.order[1]] + list(good.order[1:])))
    fs = layout_invariants.check_layout("jacobi-1d", (6, 6), a, result=bad)
    assert any(f.rule == "LAY301" for f in fs)

    lied = dataclasses.replace(good, read_bursts=good.read_bursts + 1)
    fs = layout_invariants.check_layout("jacobi-1d", (6, 6), a, result=lied)
    assert any(f.rule == "LAY302" for f in fs)


# ---------------------------------------------------------------------------
# clean-tree runs
# ---------------------------------------------------------------------------

def test_layout_invariants_clean_on_zoo():
    assert layout_invariants.run_pass() == []


def test_obs_discipline_clean_on_tree():
    fs = obs_discipline.analyze_tree(os.path.join(REPO, "src", "repro"))
    assert fs == []


def test_data_types_table_clean():
    assert access.check_data_types() == []


def test_selftest_all_rules_fire():
    st = runner.selftest()
    assert st["ok"], st["fired"]
    assert set(st["fired"]) >= {"misaligned-pack", "obs-under-jit",
                                "invalid-permutation", "burst-miscount"}


# ---------------------------------------------------------------------------
# CLI: exit codes and baseline workflow (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_clean_tree_exits_zero(tmp_path):
    out = str(tmp_path / "report.json")
    r = _cli(["--json", out])
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        report = json.load(f)
    assert report["n_new"] == 0


@pytest.mark.slow
def test_cli_violation_exits_nonzero_until_suppressed(tmp_path):
    badroot = tmp_path / "badpkg"
    badroot.mkdir()
    (badroot / "bad.py").write_text(runner.OBS_UNDER_JIT_SRC)
    r = _cli(["--root", str(badroot)])
    assert r.returncode == 1
    assert "OBS201" in r.stdout

    # suppression workflow: record the baseline, rerun -> green
    base = str(tmp_path / "baseline.json")
    r = _cli(["--root", str(badroot),
              "--baseline", base, "--write-baseline"])
    assert r.returncode == 0
    r = _cli(["--root", str(badroot), "--baseline", base])
    assert r.returncode == 0
    assert "suppressed OBS201" in r.stdout


@pytest.mark.slow
def test_cli_selftest_exits_zero():
    r = _cli(["--selftest"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "selftest: ok" in r.stdout
