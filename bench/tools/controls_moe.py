#!/usr/bin/env python3
"""Readings that set the MoE training cell's limits: the program and its
controls.

    python3 bench/tools/controls_moe.py --workload hprot_dsv2lite_save \\
        --seeds 1 2

For each seed, at the cell's own size, in one process, this prints the
numbers the cell's check compares, each against the float32 reference:
the program's checked steps (set up as the cell runs them), the control
(the reference computed in float8_e4m3fn, the precision below the
configuration's bfloat16) and the fault "half of the batch left out" (the
reference over half the rows); ``routing_flip_share`` for the program and
for the float8 control. Each reading is judged against the traffic
file's limits by the cell's own comparison (``hprot_moe.limited_checks``)
and prints ``correct`` and the checks it failed: the program must read
correct, the control and the fault not. A last line gives, per number,
the program's largest reading and the control's and the fault's least.
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

KEYS = ("grad_norm_gap", "change_norm_gap", "embed_change_gap",
        "loss_rel_gap", "worst_grad_leaves", "worst_change_leaves")


def moe_readings(spec: dict, seed: int, devices, scratch: str,
                 control: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from drivers import hprot_moe
    cell = hprot_moe.Cell(spec["config"], spec["traffic"], seed=seed,
                          scratch=scratch, devices=devices)
    cell.setup()
    cell.ckpt.close()
    for leaf in jax.tree.leaves(cell.state):
        leaf.delete()
    cell.state = None
    cell.program.change = cell.program_change()
    ref = cell.reference()

    limits = spec["traffic"]["limits"]

    def judged(gaps, flips=None):
        checks = hprot_moe.limited_checks(gaps, limits, flips)
        failed = [k for k, v, lim in checks if v > lim]
        return {**{k: gaps[k] for k in KEYS},
                **({} if flips is None else {"routing_flip_share": flips}),
                "correct": not failed, "failed": failed}
    out = {"program": judged(cell.program.gaps(ref),
                             cell.routing_flip_share())}
    if control:
        f8 = jnp.float8_e4m3fn
        out["control"] = judged(cell.reference(f8).gaps(ref),
                                cell.routing_flip_share(f8))
    half = spec["traffic"]["global_batch"] // 2

    def half_batch(i):
        return {k: v[:half] for k, v in cell.batch(i).items()}
    out["half_batch"] = judged(cell.reference(batch=half_batch).gaps(ref))
    return out


def summary(outs: list) -> dict:
    """Per compared number: the program's largest reading, and the least
    of the control's and of the fault's, over the seeds."""
    keys = ("grad_norm_gap", "change_norm_gap", "embed_change_gap",
            "routing_flip_share")
    got: dict = {}
    for what, pick in (("program", max), ("control", min),
                       ("half_batch", min)):
        for k in keys:
            vals = [o[what][k] for o in outs if k in o.get(what, {})]
            if vals:
                got.setdefault(k, {})[what] = pick(vals)
    return got


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    from repro.runtime import setup_compile_cache
    spec = run_cell.cell_spec(args.workload)
    devices = run_cell.require_chips(spec["workload"]["chips"])
    setup_compile_cache()
    scratch = os.path.join(run_cell.SCRATCH, "controls")
    outs = []
    try:
        for seed in args.seeds:
            shutil.rmtree(scratch, ignore_errors=True)
            os.makedirs(scratch)
            outs.append(moe_readings(spec, seed, devices, scratch))
            print(json.dumps({"seed": seed, **outs[-1]}), flush=True)
        print(json.dumps({"summary": summary(outs)}), flush=True)
    finally:
        shutil.rmtree(run_cell.SCRATCH, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
