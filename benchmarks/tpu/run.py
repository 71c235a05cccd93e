#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout.  Everything else is found by name: the configuration file it
names, ``traffic/<traffic>.json`` (whose ``driver`` key picks
``drivers/<driver>.py``), and one reader ``metrics/<metric>.py`` for each
per-layer metric.  Adding a cell, a configuration or a metric adds files
and manifest entries and edits none.

The run makes its inputs and weights from ``--seed``, warms up every shape
it will use (set-up), measures for ``--seconds`` (tracing off: the cell's
end-to-end metrics; ``--trace 1``: its per-layer metrics from a profiler
trace of a shorter window), checks what the timed path produced against a
plain reference, and prints one JSON line last on standard output.  Without
a TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".traces")
sys.path.insert(0, HERE)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str):
    """(cell, config entry, end-to-end metrics, per-layer metrics)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return cell, config, e2e, layer


class CompileLog:
    """Backend compilations and persistent-cache lookups, as JAX reports them."""

    def __init__(self, jax):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def measure(driver, seconds: float, max_units: int = 0):
    """Units started until ``seconds`` have passed (or ``max_units`` ran);
    returns the window's length, from the first unit's start to the end of
    all the work sent (a driver that keeps work in flight waits for it in
    ``drain``)."""
    import jax
    units = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        while True:
            driver.unit()
            units += 1
            if max_units and units >= max_units:
                break
            if time.perf_counter() - t0 >= seconds:
                break
        if hasattr(driver, "drain"):
            driver.drain()
    return time.perf_counter() - t0


def traced_window(driver, seconds: float, traffic: dict, trace_dir: str):
    """The traced run's window under the profiler: ``trace_units`` units,
    or, where one unit is too long to trace whole, the part of one unit that
    the driver's ``traced_unit`` traces.  Returns the window's length."""
    import jax

    def start():
        jax.profiler.start_trace(trace_dir)

    if hasattr(driver, "traced_unit"):
        return driver.traced_unit(start, jax.profiler.stop_trace)
    start()
    window_s = measure(driver, seconds, int(traffic["trace_units"]))
    jax.profiler.stop_trace()
    return window_s


def driver_class(traffic: dict):
    """The ``Driver`` of ``drivers/<traffic's driver>.py``."""
    name = traffic["driver"]
    return load_module(os.path.join(HERE, "drivers", name + ".py"),
                       "driver_" + name).Driver


def run_cell(workload: str, config: dict, traffic: dict, e2e, layer,
             seed: int, seconds: float, trace_on: bool, devices, chip_peaks,
             t_process: float, trace_dir: str = TRACE_DIR):
    """Set-up, window and check of one cell; returns (result dict, set-up
    line, checks)."""
    import jax
    log = CompileLog(jax)
    driver = driver_class(traffic)(config, traffic, seed, devices)
    driver.setup()
    setup_s = time.perf_counter() - t_process
    setup_compile = (log.seconds, log.compiles, log.hits, log.misses)

    if trace_on:
        shutil.rmtree(trace_dir, ignore_errors=True)
        window_s = traced_window(driver, seconds, traffic, trace_dir)
    else:
        window_s = measure(driver, seconds)
    window_compiles = log.compiles - setup_compile[1]
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {}
    if trace_on:
        import devtrace
        record = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir)
        os.makedirs(trace_dir)
        devtrace.save(record, os.path.join(trace_dir, workload + ".json.gz"))
        device["busy_s"] = devtrace.busy_s(record)
        device["window_s"] = record["window_ns"] / 1e9
        ctx = {"trace": record, "counts": driver.layer_counts(chip_peaks),
               "peaks": chip_peaks, "chips": len(devices),
               "window_s": window_s, "config": config,
               "hlo": getattr(driver, "hlo_text", lambda: "")}
        metrics = {}
        for m in layer:
            reader = load_module(
                os.path.join(HERE, "metrics", m["name"] + ".py"),
                "metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            else:
                print(f"metric {m['name']}: its reader found nothing to read"
                      f" in a trace of {len(record['devices'])} device(s)",
                      file=sys.stderr, flush=True)
        result["breakdown"] = {"device_ops": devtrace.top_ops(record),
                               "idle_gaps": devtrace.idle_gaps(record)}
    else:
        values = driver.e2e(window_s)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}

    checks = driver.check() + [("window_compiles", window_compiles, 0)]
    out = {"correct": all(passes(c) for c in checks),
           "attempted": driver.attempted, "failed": driver.failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {c[0]: {"value": c[1], "limit": c[2]} for c in checks}
    setup_line = (f"set-up: {setup_s:.3f} s; compile {setup_compile[0]:.2f} s "
                  f"in {setup_compile[1]} compilations, persistent cache "
                  f"{setup_compile[2]} hits / {setup_compile[3]} misses; "
                  f"compilations in the window: {window_compiles}")
    return out, setup_line, checks


def passes(check) -> bool:
    """(name, value, limit) holds where value <= limit; a fourth element
    ``"min"`` turns the limit into a floor."""
    if len(check) > 3 and check[3] == "min":
        return check[1] >= check[2]
    return check[1] <= check[2]


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(workload: str) -> dict:
    """Everything a run of ``workload`` needs before its driver is built:
    the cell's entries and files, its TPU devices, the chip's peaks, and
    JAX's persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says).  Raises ``NoChip`` without a TPU."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config_entry, e2e, layer = cell_spec(bench, workload)
    chips = int(cell["chips"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"cell {workload} needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, os.path.join(ROOT, "src"))     # the system under test

    import peaks
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"cell": cell, "config": config, "traffic": traffic, "e2e": e2e,
            "layer": layer, "devices": devices[:chips],
            "peaks": peaks.lookup(devices[0].device_kind)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = load_cell(args.workload)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    out, setup_line, checks = run_cell(
        args.workload, spec["config"], spec["traffic"], spec["e2e"],
        spec["layer"], args.seed, args.seconds, bool(args.trace),
        spec["devices"], spec["peaks"], T_PROCESS)
    print(setup_line, flush=True)
    for c in checks:
        print(f"check {c[0]}: {c[1]!r} ({'at least' if len(c) > 3 else 'limit'}"
              f" {c[2]!r}) {'ok' if passes(c) else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
