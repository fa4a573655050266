#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, its control, its faults.

    python3 bench/tools/controls.py --workload hprot_stablelm_save \\
        --seeds 1 2 3

For each seed, at the cell's own size, in one process, this prints the
numbers the cell's check compares:

* ``hdep`` cells: the control, the float64 host reducers over fields
  rounded to bfloat16 (the precision below the staged float32), against
  the float64 reference; histograms against the reference over the
  float32-rounded fields, as the check compares them.
* ``hprot`` cells: the program's checked steps (set-up as the cell runs
  it), the control (the reference computed in float8_e4m3fn, the
  precision below the configuration's bfloat16), and the fault "half of
  the batch left out" (the reference over half the rows), each against
  the float32 reference. The fault "state returned unchanged" reads 1 on
  the change by construction and is not run.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run_cell  # noqa: E402


def hdep_readings(spec: dict, seed: int) -> dict:
    import numpy as np

    from ref import orion, reducers
    specs = spec["traffic"]["reducers"]
    gen = spec["config"]["generator"]
    base = orion.generate(gen, gen["structure_seed"])
    snap = orion.variants(base, 1, spec["traffic"]["perturbation"], seed)[0]
    full = reducers.run_dag(specs, snap)
    cast = reducers.run_dag(specs, reducers.round_fields(snap, np.float32))
    import ml_dtypes
    ctrl = reducers.run_dag(specs, reducers.round_fields(
        snap, ml_dtypes.bfloat16))
    out = {}
    for s, f, c, k in zip(specs, full, cast, ctrl):
        err, bad = reducers.compare(k, c if s["kind"] == "hist" else f)
        out[s["check"]] = {"control": err, "control_mismatches": bad}
    return out


def hprot_readings(spec: dict, seed: int, devices, scratch: str,
                   control: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from drivers import hprot
    cell = hprot.Cell(spec["config"], spec["traffic"], seed=seed,
                      scratch=scratch, devices=devices)
    cell.setup()
    cell.ckpt.close()
    for leaf in jax.tree.leaves(cell.state):
        leaf.delete()
    cell.state = None
    cell.program.change = cell.program_change()
    ref = cell.reference()
    out = {"program": cell.program.gaps(ref)}
    if control:
        out["control"] = cell.reference(jnp.float8_e4m3fn).gaps(ref)
    full_batch = cell.batch
    half = spec["traffic"]["global_batch"] // 2
    cell.batch = lambda i: {k: v[:half] for k, v in full_batch(i).items()}
    out["half_batch"] = cell.reference().gaps(ref)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=3,
                   help="run the hprot control on the first N seeds only")
    args = p.parse_args()
    spec = run_cell.cell_spec(args.workload)
    driver = spec["traffic"]["driver"]
    devices = None
    if driver == "hprot":
        from repro.runtime import setup_compile_cache
        devices = run_cell.require_chips(spec["workload"]["chips"])
        setup_compile_cache()
    scratch = os.path.join(run_cell.SCRATCH, "controls")
    try:
        for i, seed in enumerate(args.seeds):
            shutil.rmtree(scratch, ignore_errors=True)
            os.makedirs(scratch)
            out = hdep_readings(spec, seed) if driver == "hdep" else \
                hprot_readings(spec, seed, devices, scratch,
                               control=i < args.control_seeds)
            print(json.dumps({"seed": seed, **out}), flush=True)
    finally:
        shutil.rmtree(run_cell.SCRATCH, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
