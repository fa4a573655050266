#!/usr/bin/env python3
"""Time the program's float64 host reducers on one cell's snapshot.

    python3 bench/tools/host_baseline.py --workload hdep_orion_r512

The baseline the device path has to beat: the cell's reducer DAG run
through ``ReducerDAG`` (the engine's host path) on the host CPU, per
reducer and in all, on one snapshot of the cell's configuration, twice.
It needs no chip and is not a cell.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run_cell  # noqa: E402
from drivers.hdep import program_reducer  # noqa: E402
from ref import orion  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    args = p.parse_args()
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot
    spec = run_cell.cell_spec(args.workload)
    t0 = time.perf_counter()
    gen = spec["config"]["generator"]
    arrays = orion.generate(gen, gen["structure_seed"])
    gen_s = time.perf_counter() - t0
    by_id, reducers = {}, []
    for s in spec["traffic"]["reducers"]:
        r = program_reducer(s, by_id)
        reducers.append(r)
        if s.get("id"):
            by_id[s["id"]] = r
    dag = ReducerDAG(reducers)
    snap = Snapshot(step=1, kind="amr", arrays=arrays)
    print(f"[baseline] cpus={os.cpu_count()} nodes={arrays['refine'].size} "
          f"leaves={int((~arrays['refine']).sum())} generate_s={gen_s!r}")
    for rep in range(2):
        outputs, per = {}, {}
        t_all = time.perf_counter()
        for r in dag:
            t = time.perf_counter()
            outputs[r.name] = r.reduce(snap, outputs)
            per[r.name] = time.perf_counter() - t
        total = time.perf_counter() - t_all
        print(f"[baseline] run={rep} dag_s={total!r} " + " ".join(
            f"{k}={v!r}" for k, v in per.items()), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
