"""Beyond-paper: static analyzer as a gated bench section.

Runs the ``repro.analysis`` selftest (every rule family must fire on an
injected violation — the gate is only trustworthy if it can fail), then
the full three-pass run in-process, and publishes the ``analysis/*``
series so ``repro.obs.regress`` fails CI on any new finding even when
nobody invoked the CLI.
"""
from repro.analysis import runner


def run():
    st = runner.selftest()
    if not st["ok"]:
        missed = [k for k, v in st["fired"].items() if not v]
        raise RuntimeError(f"analysis selftest missed: {missed}")
    report = runner.run_all()
    runner.publish_report(report)
    print(runner.render_report(report))
    if report["n_new"]:
        raise RuntimeError(
            f"{report['n_new']} new static-analysis finding(s)")


if __name__ == "__main__":
    run()
