#!/usr/bin/env python3
"""Run one cell traced and show what its profiler trace holds.

    python3 bench/tools/look.py --workload hdep_orion_r512 --seed 3 \\
        --seconds 10 [--keep look.xplane.pb]

Prints each trace plane with its lines and event counts, the most
frequent and the longest device op names inside the window, and the
host annotations found; then the cell's result line. This is how the
kernel names in ``bench/work.py`` were read off a chip's trace.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run_cell  # noqa: E402
from ref import devtrace  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", default=None)
    args = p.parse_args()
    find = devtrace.find_xplane

    def find_and_show(trace_dir):
        path = find(trace_dir)
        if path is None:
            print("[look] no xplane file", flush=True)
            return None
        if args.keep:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep)),
                        exist_ok=True)
            shutil.copy(path, args.keep)
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            lines = [(ln.name, sum(1 for _ in ln.events))
                     for ln in plane.lines]
            print(f"[look] plane {plane.name!r} lines {lines[:12]}")
        tr = devtrace.load(path, ("submit", "drain", "train_step", "save"))
        for dev, ops in tr["devices"].items():
            count = collections.Counter(n for n, _, _ in ops)
            secs = collections.Counter()
            for n, a, b in ops:
                secs[n] += b - a
            print(f"[look] {dev}: {len(ops)} ops")
            for n, s in secs.most_common(25):
                print(f"[look]   {s:10.6f} s  x{count[n]:6d}  {n}")
            if ops:
                print(f"[look]   first op at {min(a for _, a, _ in ops):.6f}"
                      f" last end {max(b for _, _, b in ops):.6f}")
        names = collections.Counter()
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    names.update(ev.name for ev in line.events)
        print(f"[look] host events: {names.most_common(30)}")
        win = [(a, b) for n, a, b in tr["host"] if n == devtrace.WINDOW]
        print(f"[look] window {win}", flush=True)
        return path

    devtrace.find_xplane = find_and_show
    spec = run_cell.cell_spec(args.workload)
    result = run_cell.run(spec, args.seed, args.seconds, True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
