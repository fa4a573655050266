#!/usr/bin/env python3
"""Run one cell traced and read the program's spans against the device.

    python3 bench/tools/spanclock.py --workload hdep_orion_r512 \\
        --seed 3 --seconds 30 [--out DIR] [--profiler 0]

One ``--trace 1`` run of the cell, as ``run_cell.py`` makes it, keeping
its profiler trace; then, from that trace and the program's spans:

* ``submit_offset_us``: where a driver opens a ``submit`` annotation
  around the program's ``submit`` span, the start of the program's span
  on the trace less the start of the driver's, over every such pair on
  one thread (median and maximum): the program's spans are on the
  device trace's clock;
* ``idle_by_span``: the window's device-idle seconds by innermost open
  program span (``ref/spanidle.py``), and the share under no span;
* the union of each span name over the window, and of the four parts
  of the save's gather against ``ckpt.stage`` (``hprot.gather_s``);
* the ``jit.compile`` spans in the window.

Prints one ``[spanclock]`` JSON line, then the cell's result line; with
``--out``, writes both there. ``--profiler 0`` runs the cell's set-up and
window with the program's tracer on and no profiler session, and prints
the window's end-to-end numbers and each span's union per snapshot (or
per window): what the spans read, and cost, without the profiler's own
toll on the host. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run_cell  # noqa: E402
from ref import devtrace, intervals, spanidle  # noqa: E402


def nested_offsets(path: str, name: str) -> list[float]:
    """Start of each inner ``name`` event less the start of the ``name``
    event that encloses it on the same host thread, in microseconds."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name == name)
            for (a0, a1), (b0, b1) in zip(evs, evs[1:]):
                if a0 <= b0 and b1 <= a1:
                    out.append((b0 - a0) / 1e3)
    return out


def span_report(spans: list[dict], path: str) -> dict:
    def iv(name):
        return [(s["ts"] * 1e-6, (s["ts"] + s["dur"]) * 1e-6)
                for s in spans if s["name"] == name]
    names = sorted({s["name"] for s in spans})
    unions = {n: intervals.union(iv(n)) for n in names}
    counts = {n: len(iv(n)) for n in names}
    gather = iv("ckpt.stage")
    parts = [x for n in spanidle.GATHER_SPANS for x in iv(n)]
    offsets = nested_offsets(path, "submit")
    idle = spanidle.idle_by_span(devtrace.load(path, spanidle.PROGRAM_SPANS))
    total_idle = sum(idle.values()) if idle else 0.0
    return {
        "submit_pairs": len(offsets),
        "submit_offset_us": ({"median": statistics.median(offsets),
                              "max": max(offsets), "min": min(offsets)}
                             if offsets else None),
        "idle_by_span": idle,
        "idle_s": total_idle,
        "idle_none_share": (idle.get("none", 0.0) / total_idle
                            if total_idle else None),
        "span_union_s": unions,
        "span_count": counts,
        "gather_s": intervals.union(gather) if gather else None,
        "gather_parts_s": intervals.union(parts) if parts else None,
        "compiles": [s["args"].get("fun") for s in spans
                     if s["name"] == "jit.compile"],
    }


def spans_only(spec: dict, seed: int, seconds: float) -> dict:
    """The cell's window with the tracer on and no profiler session."""
    from repro.obs import TRACER
    from repro.runtime import setup_compile_cache
    traffic = spec["traffic"]
    devices = run_cell.require_chips(spec["workload"]["chips"])
    setup_compile_cache()
    driver = run_cell.load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"]).Cell(
            spec["config"], traffic, seed=seed, scratch=run_cell.SCRATCH,
            devices=devices)
    shutil.rmtree(run_cell.SCRATCH, ignore_errors=True)
    os.makedirs(run_cell.SCRATCH)
    try:
        driver.setup()
        TRACER.enable()
        TRACER.clear()
        try:
            e2e = driver.window(seconds)
        finally:
            TRACER.disable()
        spans = TRACER.spans()
        checks, attempted, failed = driver.verify()
        counts = driver.counts()
    finally:
        driver.close()
    n = counts.get("snapshots") or 1
    names = sorted({s["name"] for s in spans})
    return {"e2e": e2e, "per": n, "correct": failed == 0 and all(
                v <= lim for _, v, lim in checks),
            "span_union_per_s": {
                name: intervals.union(
                    [(s["ts"] * 1e-6, (s["ts"] + s["dur"]) * 1e-6)
                     for s in spans if s["name"] == name]) / n
                for name in names}}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None)
    p.add_argument("--profiler", type=int, choices=(0, 1), default=1)
    args = p.parse_args()
    spec = run_cell.cell_spec(args.workload)
    if not args.profiler:
        try:
            report = spans_only(spec, args.seed, args.seconds)
        finally:
            shutil.rmtree(run_cell.SCRATCH, ignore_errors=True)
        print("[spanclock] " + json.dumps(report), flush=True)
        return 0
    keep = os.path.join(BENCH, ".scratch_keep")
    shutil.rmtree(keep, ignore_errors=True)
    os.makedirs(keep)
    find = devtrace.find_xplane
    kept = {}

    def find_and_keep(trace_dir):
        path = find(trace_dir)
        if path is not None and "path" not in kept:
            kept["path"] = shutil.copy(path, os.path.join(
                keep, "window.xplane.pb"))
        return path

    devtrace.find_xplane = find_and_keep
    from repro.obs import TRACER
    try:
        result = run_cell.run(spec, args.seed, args.seconds, True)
        report = span_report(TRACER.spans(), kept["path"])
        report.update(workload=args.workload, seed=args.seed)
        line = json.dumps(report)
        print("[spanclock] " + line, flush=True)
        print(json.dumps(result), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            stem = os.path.join(args.out, f"{args.workload}_{args.seed}")
            with open(stem + ".json", "w") as f:
                json.dump({"spanclock": report, "result": result}, f)
    finally:
        devtrace.find_xplane = find
        shutil.rmtree(keep, ignore_errors=True)
        shutil.rmtree(run_cell.SCRATCH, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
