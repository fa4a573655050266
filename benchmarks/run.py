"""Benchmark harness entry point: ``python -m benchmarks.run``.

One function per paper table/figure (DESIGN.md §9). Output format:
``name,us_per_call,derived`` CSV on stdout; ``--json PATH`` additionally
writes every record (schema: ``benchmarks/common.py``) plus floor
verdicts — the file CI archives as the ``BENCH_<PR>.json`` trajectory
artifact. ``--only a,b`` restricts to a subset of bench modules.

Floors: a module listed in :data:`FLOORS` must ``run()``-return at least
its floor value (today: the unified-API indexed-read speedup ≥5x); a
shortfall is a regression and fails the harness like an exception would.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import traceback

from repro.runtime import setup_compile_cache

from . import common

#: module name -> minimum acceptable ``run()`` return value
FLOORS = {"bench_api": 5.0,
          # async checkpoint stall must be <= 0.5x the sync save wall
          # (bench_checkpoint returns sync_stall / async_stall)
          "bench_checkpoint": 2.0}

#: record name -> maximum acceptable emitted value (checked when the
#: record exists; an absent record means its module was deselected or
#: already failed with a traceback)
CEILINGS = {"insitu.obs_overhead_pct": 2.0,
            # sharded mesh reduction: no device may hold more than ~1/N
            # (+ padding slack) of the leaf table at the 4-device bench
            "insitu.mesh_peak_leaf_frac": 0.6,
            # durable telemetry footprint (measures ~3 kB/step at the
            # bench's per-batch flush cadence; 4x headroom): a ledger
            # that silently bloats its flushes fails here, not in prod
            "obs.ledger_bytes_per_step": 12288.0}

#: record name -> minimum acceptable emitted value, same existence
#: semantics as CEILINGS (today: the serving engine must coalesce a
#: 64-viewer herd down by at least 5x vs per-request decode+merge)
RECORD_FLOORS = {"insitu.serve_coalesce_ratio_c64": 5.0}


def _modules():
    from . import (bench_api, bench_boolcodec, bench_checkpoint,
                   bench_fpdelta, bench_insitu, bench_io_scaling,
                   bench_pruning, bench_roofline)
    return [bench_pruning, bench_boolcodec, bench_fpdelta,
            bench_io_scaling, bench_api, bench_checkpoint,
            bench_insitu, bench_roofline]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write machine-readable records + floor verdicts")
    p.add_argument("--only", default=None, metavar="A,B",
                   help="comma-separated bench module names "
                        "(e.g. bench_api,bench_insitu)")
    args = p.parse_args(argv)
    setup_compile_cache()

    modules = _modules()
    if args.only:
        want = {w if w.startswith("bench_") else f"bench_{w}"
                for w in args.only.split(",") if w}
        names = {m.__name__.rsplit(".", 1)[-1] for m in modules}
        unknown = want - names
        if unknown:
            print(f"unknown bench module(s) {sorted(unknown)}; "
                  f"available: {sorted(names)}", file=sys.stderr)
            return 2
        modules = [m for m in modules
                   if m.__name__.rsplit(".", 1)[-1] in want]

    print("name,us_per_call,derived")
    failures, floors = [], {}
    for mod in modules:
        name = mod.__name__.rsplit(".", 1)[-1]
        try:
            result = mod.run()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            continue
        floor = FLOORS.get(name)
        if floor is not None:
            ok = result is not None and float(result) >= floor
            floors[name] = {"floor": floor,
                            "value": None if result is None
                            else float(result),
                            "ok": ok}
            if not ok:
                failures.append(f"{name}<floor {floor}")

    ceilings, record_floors = {}, {}
    by_name = {r["name"]: r for r in common.RECORDS}
    for rname, cap in CEILINGS.items():
        rec = by_name.get(rname)
        if rec is None:
            continue
        ok = float(rec["value"]) <= cap
        ceilings[rname] = {"ceiling": cap, "value": float(rec["value"]),
                           "ok": ok}
        if not ok:
            failures.append(f"{rname}>ceiling {cap}")
    for rname, floor in RECORD_FLOORS.items():
        rec = by_name.get(rname)
        if rec is None:
            continue
        ok = float(rec["value"]) >= floor
        record_floors[rname] = {"floor": floor,
                                "value": float(rec["value"]), "ok": ok}
        if not ok:
            failures.append(f"{rname}<floor {floor}")

    if args.json:
        payload = {
            "schema": "bench-record/v1",
            "python": platform.python_version(),
            "platform": platform.platform(),
            "records": common.RECORDS,
            "floors": floors,
            "record_floors": record_floors,
            "ceilings": ceilings,
            "failures": failures,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records -> {args.json}",
              file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
