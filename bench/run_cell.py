#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run_cell.py --workload hdep_orion_r512 --seed 7 \\
        --seconds 45 --trace 0

Everything is found by name from ``BENCHMARK.json`` at the checkout
root: the cell's configuration in ``bench/configs/<config>.json``, its
traffic mix in ``bench/traffic/<traffic>.json``, the driver the mix names
in ``bench/drivers/<driver>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py`` (a metric split by the end-to-end metric it
moves, ``<base>.<part>``, is read by ``<base>.py`` unless it has a reader
of its own). A new configuration, mix, driver or metric is new files and
a new entry in ``BENCHMARK.json``; this file stays.

A run: set-up (the driver builds its inputs from ``--seed``, starts the
system under test and warms every shape the window uses), then the
measured window of ``--seconds``, then the check of what the window
produced against the plain references in ``bench/ref``. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` records a profiler
trace and the program's spans over the window and reports its per-layer
metrics. The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the ``checks`` key of that object. The run exits non-zero with
no such line when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: fixed scratch root on the checkout's own disk; removed at exit
SCRATCH = os.path.join(BENCH, ".scratch")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell needs."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader_path(metric: str) -> str:
    """The file that reads a per-layer metric: ``metrics/<metric>.py``,
    else, for ``<base>.<part>``, the reader of ``<base>``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    base = metric.rsplit(".", 1)[0]
    if base != metric and not os.path.isfile(path):
        return reader_path(base)
    return path


def cell_spec(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in reported]
    return {"workload": cell,
            "config": _read_json(os.path.join(root, config["file"])),
            "traffic": _read_json(os.path.join(
                BENCH, "traffic", cell["traffic"] + ".json")),
            "end_to_end": e2e, "per_layer": layer}


def require_chips(n: int, platform: str = "tpu"):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}")
    if devices[0].platform != platform:
        raise NoChip(f"JAX runs on {devices[0].platform!r}; this benchmark "
                     f"measures {platform!r} chips")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


class Context:
    """What a per-layer metric reader sees: the program's spans over the
    window, the reduced device trace, the driver's counts, the device's
    peaks and the end-to-end numbers of the same run."""

    def __init__(self, *, spans, trace, counts, peaks, e2e):
        self.spans = spans
        self.trace = trace
        self.counts = counts
        self.peaks = peaks
        self.e2e = e2e

    def span_intervals(self, name: str) -> list[tuple[float, float]]:
        """``(start_s, end_s)`` of every program span called ``name``."""
        return [(s["ts"] * 1e-6, (s["ts"] + s["dur"]) * 1e-6)
                for s in self.spans if s["name"] == name]


def run(spec: dict, seed: int, seconds: float, trace: bool, *,
        platform: str = "tpu", scratch: str = SCRATCH) -> dict:
    """One run of a cell; returns the result object. ``platform`` is the
    chip the run insists on (tests rehearse on ``"cpu"``)."""
    import jax
    sys.path.insert(0, BENCH)
    from ref import devtrace
    from ref.compile_meter import CompileMeter
    import work

    from repro.obs import TRACER
    from repro.runtime import setup_compile_cache

    cell = spec["workload"]
    devices = require_chips(cell["chips"], platform)
    setup_compile_cache()
    meter = CompileMeter()
    traffic = spec["traffic"]
    driver_mod = load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    driver = driver_mod.Cell(spec["config"], traffic, seed=seed,
                             scratch=scratch, devices=devices)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T_START
        trace_dir = os.path.join(scratch, "trace")
        if trace:
            TRACER.enable()
            TRACER.clear()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # the drivers' annotations only, not the runtime's own host
            # events, which slow a host-bound window
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = meter.mark()
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                e2e = driver.window(seconds)
        finally:
            window_compiles = meter.since(mark)
            if trace:
                jax.profiler.stop_trace()
                TRACER.disable()
        spans = TRACER.spans() if trace else []
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        print(f"[window] compiles={window_compiles['compiles']} "
              f"compile_s={window_compiles['compile_s']!r} "
              f"cache_hits={window_compiles['cache_hits']} "
              f"setup_compiles={mark[1]} setup_compile_s={mark[0]!r}",
              file=sys.stderr, flush=True)
        checks, attempted, failed = driver.verify()
    finally:
        driver.close()

    kind = devices[0].device_kind
    result = {"correct": failed == 0 and all(v <= lim for _, v, lim in checks),
              "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if not trace:
        values = {**e2e, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    else:
        path = devtrace.find_xplane(trace_dir)
        reduced = devtrace.reduce(devtrace.load(path, driver.ANNOTATIONS)) \
            if path else None
        if reduced is None:
            raise RuntimeError("the traced window holds no device operation")
        ctx = Context(spans=spans, trace=reduced, counts=driver.counts(),
                      peaks=work.peaks(kind) if platform == "tpu" else None,
                      e2e=e2e)
        metrics = {}
        for m in spec["per_layer"]:
            reader = load_module(reader_path(m["name"]),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {
            "device_ops": devtrace.top(reduced["ops"]),
            "idle_gaps": devtrace.top(reduced["idle"])}
    shutil.rmtree(scratch, ignore_errors=True)
    result.update(metrics=metrics, device=device,
                  checks={name: {"value": v, "limit": lim}
                          for name, v, lim in checks})
    for name, v, lim in checks:
        print(f"[check] {name} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        spec = cell_spec(args.workload)
        result = run(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run_cell: no chip: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
