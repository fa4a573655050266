"""The MoE training cell (``hprot_moe`` driver) rehearsed on the CPU at
small widths, sound and with faults that must turn ``correct`` false, and
its per-layer readers on a hand-built trace.

Limits here are those of this small size, set from CPU readings of sound
runs (program at most 0.006, 0.004, 0.0007 and 0.012 flips over three
seeds) and of the float8 control; the cell's own limits are set at the
published widths on the chip (``bench/tools/controls_moe.py``).
"""
import os

import pytest

import run_cell

TINY_MOE = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_routed_experts": 4, "router_experts": 16,
            "num_experts_per_tok": 3, "n_shared_experts": 1,
            "num_hidden_layers": 3, "vocab_size": 512, "attention_chunk": 16}
TINY_MOE_LIMITS = {"grad_norm_gap": 0.05, "change_norm_gap": 0.05,
                   "embed_change_gap": 0.01, "routing_flip_share": 0.05}
WORKLOAD = "hprot_dsv2lite_save"


def tiny():
    spec = run_cell.cell_spec(WORKLOAD)
    spec["config"].update(TINY_MOE)
    spec["traffic"].update(seq_len=32, global_batch=4, limits=TINY_MOE_LIMITS)
    return spec


def run(tmp_path, seed=2**31 + 11):
    return run_cell.run(tiny(), seed, 1.0, False, platform="cpu",
                        scratch=str(tmp_path / "scratch"))


def test_sound_run_is_correct(tmp_path):
    res = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["checks"]["dropped_assignments"]["value"] == 0
    assert res["checks"]["routing_flip_share"]["value"] < 0.05
    assert set(res["metrics"]) == {"train_tokens_per_s", "ckpt_durable_s",
                                   "setup_s"}
    assert not os.path.exists(tmp_path / "scratch")


def _patch_grouped(monkeypatch, change):
    from repro.models import moe
    real = moe.grouped_matmul

    def broken(lhs, rhs, sizes, out_dtype):
        return change(real(lhs, rhs, sizes, out_dtype), sizes)
    monkeypatch.setattr(moe, "grouped_matmul", broken)


def test_held_expert_output_zeroed(monkeypatch, tmp_path):
    import jax.numpy as jnp

    def zero_first(out, sizes):
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < sizes[0], 0, out).astype(out.dtype)
    _patch_grouped(monkeypatch, zero_first)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["grad_norm_gap"]["value"] > 0.2


def test_shared_expert_left_out(monkeypatch, tmp_path):
    from repro.models import layers
    real = layers.mlp

    def no_shared(p, x, cfg):
        y = real(p, x, cfg)
        # the MoE layer calls the shared experts on flattened tokens
        return y * 0 if x.ndim == 2 else y
    monkeypatch.setattr(layers, "mlp", no_shared)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["grad_norm_gap"]["value"] > 0.2


@pytest.mark.parametrize("budget", [None, 0])
def test_cut_leaf_altered_before_the_write(monkeypatch, tmp_path, budget):
    """One leaf of the cut changed between the snapshot and the gather,
    on the device path and on the host path of the cut."""
    import numpy as np

    from repro.ckpt import manager
    real = manager.AsyncCheckpointManager._snapshot

    def altered(self, state):
        self._forced_budget = budget
        cut = real(self, state)
        name, dev, sl, shape, data = cut[0]
        cut[0][4] = np.asarray(data) + np.ones((), np.asarray(data).dtype)
        return cut
    monkeypatch.setattr(manager.AsyncCheckpointManager, "_snapshot", altered)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["restore_mismatches"]["value"] == 1


def test_control_fails(tmp_path):
    """The reference in float8, below the configuration's bfloat16, in
    the program's place: some number fails its limit."""
    import jax

    import controls_moe
    spec = tiny()
    os.makedirs(tmp_path / "c")
    out = controls_moe.moe_readings(spec, 5, jax.devices(),
                                    str(tmp_path / "c"))
    limits = spec["traffic"]["limits"]
    assert all(out["program"][k] <= lim for k, lim in limits.items())
    assert any(out["control"][k] > lim for k, lim in limits.items())
    assert any(out["half_batch"][k] > limits[k]
               for k in ("grad_norm_gap", "change_norm_gap",
                         "embed_change_gap"))
    # judged by the cell's own comparison, as the cell judges itself
    assert out["program"]["correct"] and not out["program"]["failed"]
    assert not out["control"]["correct"] and out["control"]["failed"]
    assert not out["half_batch"]["correct"]
    got = controls_moe.summary([out])
    assert got["routing_flip_share"] == {
        "program": out["program"]["routing_flip_share"],
        "control": out["control"]["routing_flip_share"]}


# ---------------------------------------------------------------- readers

def _reader(name):
    return run_cell.load_module(run_cell.reader_path(name),
                                "test_metric_" + name.replace(".", "_"))


def _ctx(ops=None, spans=(), counts=None, e2e=None):
    peaks = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    trace = {"ops": ops or {}, "busy_s": 1.0, "window_s": 2.0}
    return run_cell.Context(spans=list(spans), trace=trace,
                            counts=counts or {}, peaks=peaks, e2e=e2e or {})


def _counts():
    spec = run_cell.cell_spec(WORKLOAD)
    return {"steps": 10, "seq_len": 4096, "global_batch": 4,
            "config": spec["config"], "moe_assignments_held": 49152.0}


def test_expert_readers_on_a_synthetic_trace():
    import work_moe
    ops = {"gmm.3": (0.6, 120), "tgmm.7": (0.4, 40),
           "fusion.12": (9.0, 500), "gmm_like.1": (5.0, 1)}
    ctx = _ctx(ops, counts=_counts())
    assert _reader("hprot.expert_s").read(ctx) == pytest.approx(0.1)
    cfg = _counts()["config"]
    flops = work_moe.expert_train_flops(cfg, 49152.0)
    assert flops == pytest.approx(18 * 2048 * 1408 * 49152.0)
    want = 100 * (flops / 1.97e14) / 0.1
    assert _reader("hprot.expert_roofline").read(ctx) == pytest.approx(want)
    assert _reader("hprot.expert_s").read(_ctx({"fusion.1": (1.0, 1)},
                                               counts=_counts())) is None


def test_cut_host_and_mfu_readers():
    def span(name, a, b):
        return {"name": name, "ts": a * 1e6, "dur": (b - a) * 1e6}
    ctx = _ctx(spans=[span("ckpt.cut.host", 1.0, 3.0),
                      span("ckpt.cut.host", 2.0, 4.5),
                      span("ckpt.cut.device", 0.5, 1.0)],
               counts=_counts(), e2e={"train_tokens_per_s": 20000.0})
    assert _reader("hprot.cut_host_s").read(ctx) == pytest.approx(3.5)
    assert _reader("hprot.cut_host_s").read(_ctx()) is None
    import work_moe
    cfg = _counts()["config"]
    per_token = work_moe.dense_train_flops_per_token(cfg, 4096) \
        + work_moe.expert_train_flops(cfg, 49152.0) / 16384
    mfu = _reader("hprot.train_mfu.moe")
    assert run_cell.reader_path("hprot.train_mfu.moe").endswith(
        "hprot.train_mfu.moe.py")
    assert mfu.read(ctx) == pytest.approx(100 * per_token * 20000 / 1.97e14)
    assert 10 < mfu.read(ctx) < 100
