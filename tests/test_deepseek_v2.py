"""DeepSeek-V2 (MLA, YaRN, shared + routed experts, dropless grouped
dispatch) against the plain reference ``bench/ref/deepseek_v2.py``, on the
CPU at small widths with seeded weights.

Tolerances: program and reference both run float32 here (the program's
``compute_dtype`` set to float32, the reference at ``highest``); they sum
in different orders (chunked attention, grouped matmuls, per-sequence
gradients), so they agree to float32 round-off of a few ulps of the
largest values: 1e-5 relative on logits and loss, 1e-4 on each gradient
leaf's norm-relative difference (gradients pass through more sums).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.ref import deepseek_v2 as ref
from repro.configs import deepseek_v2_lite as dsv
from repro.configs import granite_moe_1b_a400m as granite
from repro.models import layers, moe
from repro.models.attention import mla_softmax_scale
from repro.models.transformer import LM

#: a small DeepSeek-V2 in the configuration file's keys: a dense layer,
#: two MoE layers routing top-3 over 16 experts, of which ``held`` here
TINY = {**dsv.SMOKE_HF, "num_hidden_layers": 3, "router_experts": 16,
        "n_routed_experts": 16, "num_experts_per_tok": 3,
        "rms_norm_eps": 1e-6, "aux_loss_alpha": 0.001}


def _program(hf, **kw):
    return LM(dsv.from_hf(hf, remat="none", compute_dtype="float32",
                          attn_chunk=8, **kw))


def _batch(seed=1, b=2, s=16, vocab=256):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, vocab)
    return tok[:, :-1], tok[:, 1:]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("held", [16, 4])
def test_program_matches_reference(held):
    """Logits, loss (with the balance loss) and every gradient leaf."""
    hf = {**TINY, "n_routed_experts": held, "expert_offset": 16 - held}
    lm = _program(hf)
    params = ref.init_params(hf, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, params) == \
        jax.tree.map(lambda s: s.shape, lm.abstract_params())
    tokens, labels = _batch()
    batch = {"tokens": tokens, "labels": labels}
    with jax.default_matmul_precision("highest"):
        logits = lm.forward(params, tokens)[0]
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(params, batch)
    want_loss, want_grads = ref.grads(params, tokens, labels, hf)
    assert _rel(logits, ref.logits(params, tokens, hf)) < 1e-5
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    assert float(metrics["aux"]) > 0 and int(metrics["moe_dropped"]) == 0
    gaps = jax.tree.map(_rel, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    np.testing.assert_array_equal(np.asarray(lm.routes(params, tokens)),
                                  np.asarray(ref.routes(params, tokens, hf)))


def test_yarn_against_closed_form():
    """DeepSeek-V2-Lite's published YaRN: pairs below the beta_fast
    correction dim keep theta**(-2i/64), pairs above beta_slow's are
    divided by the factor 40, a linear ramp between; the softmax scale is
    192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2 and cos/sin are unscaled."""
    cfg = dsv.CONFIG
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)

    def corr(rot):   # 64 * ln(4096 / (2 pi rot)) / (2 ln 10000)
        return 64 * math.log(4096 / (2 * math.pi * rot)) / (2 * math.log(1e4))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = layers.yarn_inv_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:low], base[:low], rtol=1e-6)
    np.testing.assert_allclose(got[high:], base[high:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert layers.yarn_cos_scale(cfg) == 1.0
    inv, cs, scale = ref.yarn({**dsv.HF, "rope_scaling": dsv.HF["rope_scaling"]})
    np.testing.assert_allclose(inv, got, rtol=1e-6)
    assert cs == 1.0 and scale == pytest.approx(mla_softmax_scale(cfg))


def _layer_input(d, t=24, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, t, d), jnp.float32)


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of one MoE layer (two experts each, routed
    over all 16), their routed parts summed and the shared expert counted
    once, give the uncut reference layer."""
    hf = {**TINY, "num_hidden_layers": 2}
    params = ref.init_params(hf, jax.random.PRNGKey(7))
    p = jax.tree.map(lambda t: t[0], params["blocks"]["moe"])
    x = _layer_input(hf["hidden_size"])
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._moe(x, p, hf, lambda a: a)
        total = layers.mlp(p["shared"], x, dsv.from_hf(
            hf, compute_dtype="float32")).astype(jnp.float32)
        for rank in range(8):
            cfg = dsv.from_hf({**hf, "n_routed_experts": 2,
                               "expert_offset": 2 * rank, "n_shared_experts": 0},
                              compute_dtype="float32")
            share = {k: p[k][2 * rank:2 * rank + 2] for k in ("wi", "wg", "wo")}
            y, _, stats = moe.moe_layer({**share, "router": p["router"]}, x, cfg)
            assert int(stats["moe_dropped"]) == 0
            total = total + y
    assert _rel(total, want) < 1e-5


def _granite(**kw):
    return dataclasses.replace(granite.SMOKE, compute_dtype="float32", **kw)


def test_dropless_equals_capacity_where_nothing_drops():
    """On granite's SMOKE layer with a capacity every expert can fill
    with all tokens (so the bucket path drops nothing), the grouped path
    gives the same output and balance loss."""
    cfg = _granite(capacity_factor=granite.SMOKE.n_experts
                   / granite.SMOKE.top_k)
    p = LM(cfg).init(jax.random.PRNGKey(2))
    p = jax.tree.map(lambda t: t[0], p["blocks"]["moe"])
    x = _layer_input(cfg.d_model)
    with jax.default_matmul_precision("highest"):
        y_cap, aux_cap = moe.moe_mlp(p, x, cfg)
        y_grp, aux_grp, stats = moe.moe_layer(
            p, x, dataclasses.replace(cfg, moe_dispatch="dropless"))
    assert _rel(y_grp, y_cap) < 1e-5
    assert float(aux_grp) == pytest.approx(float(aux_cap), rel=1e-6)
    assert int(stats["moe_assignments_held"]) == x.shape[0] * x.shape[1] \
        * cfg.top_k


def test_no_assignment_dropped_under_a_skewed_router():
    """Every token routed to the same experts: the capacity path drops
    most assignments, the grouped path computes all of them (its counter
    reads 0) and matches the reference layer."""
    hf = {**TINY, "num_hidden_layers": 2, "n_routed_experts": 4}
    params = ref.init_params(hf, jax.random.PRNGKey(9))
    p = jax.tree.map(lambda t: t[0], params["blocks"]["moe"])
    skew = jnp.zeros((16,)).at[:3].set(50.0)
    x = _layer_input(hf["hidden_size"])
    # a constant feature carries the skew: every token prefers experts 0-2
    x = x.at[..., 0].set(1.0)
    p = {**p, "router": p["router"].at[0].add(skew)}
    cfg = dsv.from_hf(hf, compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        y, _, stats = moe.moe_layer(p, x, cfg)
        want, _, ids = ref._moe(x, p, hf, lambda a: a)
    assert set(np.unique(np.asarray(ids))) == {0, 1, 2}
    t = x.shape[0] * x.shape[1]
    assert int(stats["moe_assignments_held"]) == 3 * t
    assert int(stats["moe_dropped"]) == 0
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(4 / 3)
    assert _rel(y, want) < 1e-5
    assert moe.capacity(cfg, t) < t     # buckets would drop most of them


def test_dropped_counter_counts_rows_left_uncomputed(monkeypatch):
    """The counter reads the combine: with fewer grouped-matmul rows than
    held assignments (a capacity, which the dropless path never sets) it
    counts each assignment that got no row."""
    hf = {**TINY, "num_hidden_layers": 2, "n_routed_experts": 4}
    params = ref.init_params(hf, jax.random.PRNGKey(9))
    p = jax.tree.map(lambda t: t[0], params["blocks"]["moe"])
    x = _layer_input(hf["hidden_size"]).at[..., 0].set(1.0)
    p = {**p, "router": p["router"].at[0].add(
        jnp.zeros((16,)).at[:3].set(50.0))}
    cfg = dsv.from_hf(hf, compute_dtype="float32")
    t = x.shape[0] * x.shape[1]
    monkeypatch.setattr(moe, "held_rows", lambda t, k, held: 128)
    _, _, stats = moe.moe_layer(p, x, cfg)
    assert int(stats["moe_assignments_held"]) == 3 * t > 128
    assert int(stats["moe_dropped"]) == 3 * t - 128


def test_aux_coefficient_comes_from_the_config():
    """``loss_fn`` adds ``aux_loss_alpha`` times the summed balance loss;
    granite keeps the default 0.01."""
    assert granite.CONFIG.aux_loss_alpha == 0.01
    assert dsv.CONFIG.aux_loss_alpha == 0.001
    tokens, labels = _batch()
    batch = {"tokens": tokens, "labels": labels}
    for alpha in (0.001, 0.5):
        hf = {**TINY, "aux_loss_alpha": alpha}
        lm = _program(hf)
        params = ref.init_params(hf, jax.random.PRNGKey(0))
        total, m = lm.loss_fn(params, batch)
        assert float(total) == pytest.approx(
            float(m["loss"]) + alpha * float(m["aux"]), rel=1e-6)
