"""Rehearsals of each driver on the CPU at small sizes, sound and with
the timed path broken underneath: every fault the cell can have must
turn ``correct`` false.

The harness's look for a chip is skipped (``platform="cpu"``); the rest
of a run is the one the chip runs. Limits of the training cell are those
of this small size (its bfloat16 round-off is larger than at the
published widths): set from CPU readings of sound runs at this size.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run_cell

TINY_LM = {"hidden_size": 128, "intermediate_size": 256,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "vocab_size": 512, "num_hidden_layers": 2}
TINY_LM_LIMITS = {"grad_norm_gap": 0.5, "change_norm_gap": 0.1,
                  "embed_change_gap": 0.05}


def tiny(workload):
    spec = run_cell.cell_spec(workload)
    if spec["traffic"]["driver"] == "hdep":
        spec["config"]["generator"]["max_level"] = 6
        for r in spec["traffic"]["reducers"]:
            if "resolution" in r:
                r["resolution"] = 64
    else:
        spec["config"].update(TINY_LM)
        spec["traffic"].update(seq_len=32, global_batch=4,
                               limits=TINY_LM_LIMITS)
    return spec


def run(spec, tmp_path, seed=2**31 + 7, seconds=1.0):
    return run_cell.run(spec, seed, seconds, False, platform="cpu",
                        scratch=str(tmp_path / "scratch"))


@pytest.mark.parametrize("workload", ["hdep_orion_r512", "hdep_orion_lod7",
                                      "hprot_stablelm_save"])
def test_sound_run_is_correct(workload, tmp_path):
    res = run(tiny(workload), tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}
    assert not os.path.exists(tmp_path / "scratch")


def _wrap_device_impl(monkeypatch, cls, change):
    from repro.insitu import device
    factory = device.DEVICE_IMPLS[cls]

    def broken(r):
        impl = factory(r)
        return None if impl is None else (lambda dt: change(impl, dt))
    monkeypatch.setitem(device.DEVICE_IMPLS, cls, broken)


def test_hdep_answer_altered(monkeypatch, tmp_path):
    from repro.insitu.reducers import SliceReducer

    def alter(impl, dt):
        out = impl(dt)
        return {"image": out["image"].at[3, 5].multiply(1.001)}
    _wrap_device_impl(monkeypatch, SliceReducer, alter)
    res = run(tiny("hdep_orion_r512"), tmp_path)
    assert not res["correct"]
    assert res["checks"]["slice_rel_err"]["value"] > 1e-4


def test_hdep_half_the_leaves_left_out(monkeypatch, tmp_path):
    from repro.insitu.reducers import ProjectionReducer

    def half(impl, dt):
        import jax.numpy as jnp
        ok = dt.ok
        dt._geom = (dt.coords, dt.levels,
                    ok & (jnp.arange(ok.shape[0]) % 2 == 0))
        return impl(dt)
    _wrap_device_impl(monkeypatch, ProjectionReducer, half)
    res = run(tiny("hdep_orion_r512"), tmp_path)
    assert not res["correct"]


def _wrap_train_step(monkeypatch, change):
    from repro.train import step as step_lib
    make = step_lib.make_train_step

    def broken(lm, opt):
        return change(make(lm, opt))
    monkeypatch.setattr(step_lib, "make_train_step", broken)


def test_hprot_state_unchanged(monkeypatch, tmp_path):
    def unchanged(step):
        def f(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return f
    _wrap_train_step(monkeypatch, unchanged)
    res = run(tiny("hprot_stablelm_save"), tmp_path)
    assert not res["correct"]
    assert res["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_hprot_half_the_batch(monkeypatch, tmp_path):
    def half(step):
        def f(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return f
    _wrap_train_step(monkeypatch, half)
    res = run(tiny("hprot_stablelm_save"), tmp_path)
    assert not res["correct"]


def test_hprot_control_fails(tmp_path):
    """The reference in float8, the precision below the configuration's
    bfloat16, in the program's place: some number fails its limit."""
    import jax
    import controls
    spec = tiny("hprot_stablelm_save")
    os.makedirs(tmp_path / "c")
    out = controls.hprot_readings(spec, 5, jax.devices(), str(tmp_path / "c"))
    limits = spec["traffic"]["limits"]
    assert all(out["program"][k] <= lim for k, lim in limits.items())
    assert any(out["control"][k] > lim for k, lim in limits.items())


def test_hdep_control_fails():
    """The float64 reducers over bfloat16-rounded fields, the precision
    below the staged float32, fail every limit of the mix."""
    import controls
    spec = tiny("hdep_orion_r512")
    out = controls.hdep_readings(spec, 5)
    for s in spec["traffic"]["reducers"]:
        r = out[s["check"]]
        assert r["control"] > s["limit"] or r["control_mismatches"] > 0


def test_refuses_without_a_chip(tmp_path):
    root = os.path.dirname(run_cell.BENCH)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run_cell.py", "--workload",
                        "hdep_orion_r512", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # only BENCHMARK.json and the benchmark's own files: no program
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run_cell.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run_cell.py", "--workload",
                        "hdep_orion_r512", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    json.loads(open(tmp_path / "BENCHMARK.json").read())
