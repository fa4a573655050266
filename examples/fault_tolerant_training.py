"""Fault tolerance demo: supervisor + induced crash + elastic resume.

    PYTHONPATH=src python examples/fault_tolerant_training.py

1. Launch training under the supervisor with TRAIN_CRASH_AT=7 — the child
   hard-exits mid-run (simulated node failure).
2. The supervisor relaunches; the new process restores the latest complete
   HProt context and finishes.
3. Verify the final state matches an uninterrupted run bit for bit.
"""
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.train.supervisor import run_supervised

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CKPT = "/tmp/hx_ft_demo"


def train_cmd(ckpt_dir, steps=14):
    return [sys.executable, "-m", "repro.launch.train", "--arch",
            "stablelm_1_6b", "--smoke", "--steps", str(steps),
            "--seq-len", "32", "--global-batch", "4",
            "--ckpt-every", "5", "--ckpt-dir", ckpt_dir]


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    shutil.rmtree(CKPT + "_ref", ignore_errors=True)
    # children pick their JAX platform from the caller's environment:
    # this process never touches JAX, so a child may take the chip
    env = {"PYTHONPATH": SRC}

    print("== supervised run with induced (one-off) crash at step 7")
    rc, restarts = run_supervised(train_cmd(CKPT), max_restarts=3, env=env,
                                  env_first={"TRAIN_CRASH_AT": "7"})
    print(f"   supervisor: rc={rc} restarts={restarts}")
    assert rc == 0 and restarts >= 1

    print("== uninterrupted reference run")
    subprocess.run(train_cmd(CKPT + "_ref"),
                   env={**os.environ, **env}, check=True)

    print("== compare final checkpoints bit for bit")
    from repro.hercule.checkpoint import CheckpointManager
    import numpy as np
    a = CheckpointManager(CKPT)
    b = CheckpointManager(CKPT + "_ref")
    assert a.latest_step() == b.latest_step() == 14
    # indexed views: each manifest is parsed once for the whole comparison
    va = a.db.view(14)
    vb = b.db.view(14)
    keys = {(r.name, r.domain) for r in va.records}
    assert keys == {(r.name, r.domain) for r in vb.records}
    for rec in va.records:
        wa = va.read_record(rec)
        wb = vb.read(rec.domain, rec.name)
        assert np.array_equal(wa, wb), (rec.name, rec.domain)
    print(f"   {len(keys)} tensors identical after crash+restart. OK")


if __name__ == "__main__":
    main()
