"""Mixture-of-Experts FFN with grouped sort-based dispatch (EP).

Token-choice top-k routing. Dispatch is *grouped*: tokens are split into
``cfg.moe_groups`` groups whose leading dim rides the 'data' mesh axis, so
the argsort / position-rank / scatter all stay LOCAL to a data shard (a
global sort over sharded tokens forces all-gathers — measured 2x worse
collectives, EXPERIMENTS.md §Perf i1). Capacity is per-group (standard in
EP systems). The only cross-shard movement is the expert all-to-all that
GSPMD inserts for the bucket resharding:

  * E % model == 0 (granite, 32e): experts='model' -> block-diagonal EP,
    one all-to-all of ~T*d bytes per layer.
  * E % model != 0 (mixtral, 8e): experts replicated, expert_mlp='model'
    -> Megatron TP inside each expert, all-reduce of the FFN output.

Position-in-expert uses segment starts (O(T*k)), not a one-hot cumsum
(O(T*k*E)).

``cfg.moe_dispatch == "dropless"`` (DeepSeek-V2) takes another path: the
layer holds experts ``[expert_offset, expert_offset + n_experts_held)``,
routes every token over all ``n_experts``, sorts the assignments to its
held experts and runs them as grouped matmuls (Pallas megablox ``gmm`` on
a TPU, ``lax.ragged_dot`` elsewhere) with no capacity and nothing dropped;
always-on shared experts add a plain gated MLP.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import sharding
from . import layers
from .layers import ParamSpec


def moe_spec(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.experts_held
    gated = cfg.mlp_act in ("swiglu", "geglu")
    spec = {
        "router": ParamSpec((d, cfg.n_experts), ("fsdp", None)),
        "wi": ParamSpec((e, d, f), ("experts", "expert_in", "expert_mlp")),
        "wo": ParamSpec((e, f, d), ("experts", "expert_mlp", "expert_in")),
    }
    if gated:
        spec["wg"] = ParamSpec((e, d, f), ("experts", "expert_in", "expert_mlp"))
    if cfg.n_shared_experts:
        spec["shared"] = layers.mlp_spec(
            cfg, d_ff=cfg.n_shared_experts * cfg.expert_d_ff)
    return spec


def moe_layer(p, x, cfg, *, routes: bool = False):
    """x: (B, S, D) -> (y, aux loss, stats). ``stats`` holds the dropless
    path's counters (and, with ``routes``, each token's top-k experts)."""
    if cfg.moe_dispatch == "dropless":
        return _dropless(p, x, cfg, routes)
    y, aux = _capacity_mlp(p, x, cfg)
    return y, aux, {}


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def moe_mlp(p, x, cfg):
    """x: (B, S, D) -> (B, S, D), plus aux load-balancing loss (scalar)."""
    return moe_layer(p, x, cfg)[:2]


def _capacity_mlp(p, x, cfg):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    # group only when the token count is large: grouping exists to localize
    # the big-T sort; at decode scale (T~batch) it just fragments capacity
    # (measured 3x collective regression on mixtral decode_32k, §Perf i8)
    g = math.gcd(getattr(cfg, "moe_groups", 1), t) if t >= 2048 else 1
    tl = t // g                                   # tokens per group (local)
    dt = x.dtype
    xt = x.reshape(g, tl, d)
    xt = sharding.constrain(xt, "batch", None, None)

    logits = jnp.einsum("gtd,de->gte", xt, p["router"].astype(dt),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (g, tl, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch-style), computed globally
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- grouped sort-based dispatch. GATHER-only formulation: GSPMD
    # replicates batched scatters (measured: 34 GB all-reduces of the
    # dispatch tensors, §Perf i2), but partitions batched gathers fine.
    flat_expert = expert_ids.reshape(g, tl * k)
    flat_token = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tl), k)[None], (g, tl * k))
    flat_gate = gate_vals.reshape(g, tl * k)
    order = jnp.argsort(flat_expert, axis=1)
    sorted_expert = jnp.take_along_axis(flat_expert, order, axis=1)
    sorted_token = jnp.take_along_axis(flat_token, order, axis=1)
    sorted_gate = jnp.take_along_axis(flat_gate, order, axis=1)
    # per-group segment starts: O(tl*k), no one-hot cumsum
    seg_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(e), side="left"))(
        sorted_expert)                                       # (g, E)
    seg_end = jnp.concatenate(
        [seg_start[:, 1:], jnp.full((g, 1), tl * k)], axis=1)
    cap = capacity(cfg, tl)

    # bucket slot (e, c) <- the c-th sorted assignment of expert e
    pos = seg_start[:, :, None] + jnp.arange(cap)[None, None, :]  # (g,E,cap)
    valid = pos < seg_end[:, :, None]
    pos_c = jnp.clip(pos, 0, tl * k - 1).reshape(g, e * cap)
    tok_for_slot = jnp.take_along_axis(sorted_token, pos_c, axis=1)
    vals = jnp.take_along_axis(xt, tok_for_slot[..., None], axis=1)
    be = (vals * valid.reshape(g, e * cap, 1).astype(dt)).reshape(g, e, cap, d)
    be = sharding.constrain(be, "batch", "experts", "expert_cap", "expert_in")

    # ---- expert FFN. 3D dots (e, g*cap, .) — group merged into capacity:
    # CPU's DotThunk rejects 4D bf16 batched dots, and the 3D form shards
    # identically (e->model or replicated, capacity->data).
    from .layers import wcast
    bem = be.transpose(1, 0, 2, 3).reshape(e, g * cap, d)
    wi = wcast(p["wi"], dt, "experts", "expert_in", "expert_mlp")
    h = jnp.einsum("ecd,edf->ecf", bem, wi,
                   preferred_element_type=jnp.float32)
    if cfg.mlp_act in ("swiglu", "geglu"):
        wg = wcast(p["wg"], dt, "experts", "expert_in", "expert_mlp")
        gg = jnp.einsum("ecd,edf->ecf", bem, wg,
                        preferred_element_type=jnp.float32)
        act = jax.nn.silu(gg) if cfg.mlp_act == "swiglu" else jax.nn.gelu(gg)
        h = act * h
    else:
        h = jnp.square(jax.nn.relu(h)) if cfg.mlp_act == "relu2" else jax.nn.gelu(h)
    h = sharding.constrain(h.astype(dt), "experts", "expert_cap",
                           "expert_mlp")
    wo = wcast(p["wo"], dt, "experts", "expert_mlp", "expert_in")
    out_m = jnp.einsum("ecf,efd->ecd", h, wo,
                       preferred_element_type=jnp.float32).astype(dt)
    out_e = out_m.reshape(e, g, cap, d).transpose(1, 0, 2, 3)
    out_e = sharding.constrain(out_e, "batch", "experts", "expert_cap",
                               "expert_in")
    out_flat = out_e.reshape(g, e * cap, d)

    # ---- combine: gather each assignment's slot output, un-sort via the
    # inverse permutation, then sum the k contributions per token
    pos_in_expert = (jnp.arange(tl * k)[None, :]
                     - jnp.take_along_axis(seg_start, sorted_expert, axis=1))
    keep = pos_in_expert < cap
    slot = sorted_expert * cap + jnp.minimum(pos_in_expert, cap - 1)
    contrib = jnp.take_along_axis(out_flat, slot[..., None], axis=1) \
        * (sorted_gate * keep).astype(dt)[..., None]
    inv = jnp.argsort(order, axis=1)
    unsorted = jnp.take_along_axis(contrib, inv[..., None], axis=1)
    yt = unsorted.reshape(g, tl, k, d).sum(axis=2)
    return yt.reshape(b, s, d), aux


# ------------------------------------------------------ dropless (grouped)

def seq_aux_loss(probs, ids, n_experts: int):
    """DeepSeek-V2's sequence-wise balance loss (without its alpha):
    per sequence, ``sum_e f_e * P_e`` with ``f_e`` expert e's share of the
    sequence's top-k picks times ``n_experts`` and ``P_e`` its mean router
    probability; the mean over sequences. probs (B,S,E), ids (B,S,k)."""
    b, s, k = ids.shape
    counts = jax.vmap(lambda i: jnp.zeros((n_experts,), jnp.float32)
                      .at[i.reshape(-1)].add(1.0))(ids)
    f = counts / (s * k / n_experts)
    return jnp.mean(jnp.sum(f * probs.mean(axis=1), axis=-1))


def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for ``gmm``/``tgmm``: 512-wide tiles (ragged edge
    tiles along k and n are masked) that keep a call's scoped VMEM
    under v5e's 16 MiB; m is padded to a multiple of 128."""
    return (512 if m % 512 == 0 else 128, min(512, k), min(512, n))


def grouped_matmul(lhs, rhs, sizes, out_dtype):
    """``lhs[rows of group g] @ rhs[g]`` for consecutive groups of
    ``sizes`` rows; rows past ``sum(sizes)`` are left undefined. A TPU
    runs the megablox ``gmm`` kernel, other backends ``lax.ragged_dot``."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import ops as mb
        return mb.gmm(lhs, rhs, sizes, out_dtype,
                      _gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2]))
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(out_dtype)


def held_rows(t: int, k: int, held: int) -> int:
    """Rows the grouped matmuls compute for ``t`` tokens: a token picks
    ``k`` distinct experts, so at most ``min(k, held)`` of its picks are
    held here; padded to a multiple of 128."""
    return -(-t * min(k, held) // 128) * 128


def _dropless(p, x, cfg, routes: bool):
    b, s, d = x.shape
    e, k, held = cfg.n_experts, cfg.top_k, cfg.experts_held
    t = b * s
    dt = x.dtype
    f32 = jnp.float32
    xt = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        # router in float32 over all n_experts (DeepSeek-V2's gate)
        logits = jnp.dot(xt.astype(f32), p["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, ids = jax.lax.top_k(probs, k)                   # (t, k)
        if cfg.norm_topk_prob:
            gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
        gate = gate * cfg.routed_scaling
        if cfg.aux_loss == "seq":
            aux = seq_aux_loss(probs.reshape(b, s, e), ids.reshape(b, s, k), e)
        else:
            ce = jnp.zeros((e,), f32).at[ids.reshape(-1)].add(1.0) / (t * k)
            aux = e * jnp.sum(probs.mean(axis=0) * ce)
        # the held experts' assignments first, in expert order
        local = ids.reshape(-1) - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        n_mine = mine.sum(dtype=jnp.int32)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        m = held_rows(t, k, held)
        rows = order[:m] if m <= t * k else jnp.concatenate(
            [order, jnp.zeros((m - t * k,), order.dtype)])
        valid = (jnp.arange(m) < n_mine)[:, None]
        xs = jnp.where(valid, xt[rows // k], 0).astype(dt)
    with jax.named_scope("moe.experts"):
        h = grouped_matmul(xs, p["wi"].astype(dt), sizes, dt)
        if cfg.mlp_act in ("swiglu", "geglu"):
            g = grouped_matmul(xs, p["wg"].astype(dt), sizes, dt)
            act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
            h = act(g.astype(f32)) * h.astype(f32)
        else:
            h = jnp.square(jax.nn.relu(h)) if cfg.mlp_act == "relu2" \
                else jax.nn.gelu(h)
        out = grouped_matmul(h.astype(dt), p["wo"].astype(dt), sizes, dt)
    with jax.named_scope("moe.route"):
        w = gate.reshape(-1)[rows][:, None]
        # select, not multiply: rows past n_mine hold undefined values
        contrib = jnp.where(valid, out.astype(f32) * w, 0.0).astype(dt)
        slot = jnp.argsort(order)                 # assignment -> sorted row
        # an assignment whose row the grouped matmuls did not compute
        # reads the zero row past the last one
        slot = jnp.where(slot < jnp.minimum(n_mine, m), slot, m)
        dropped = jnp.sum(mine & (slot == m), dtype=jnp.int32)
        padded = jnp.concatenate([contrib, jnp.zeros((1, d), dt)])
        y = padded[slot.reshape(t, k)].astype(f32).sum(axis=1)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + layers.mlp(p["shared"], xt, cfg).astype(f32)
    load = sizes.astype(f32)
    stats = {"moe_assignments_held": n_mine,
             "moe_load_max_over_mean": load.max() / jnp.maximum(
                 load.mean(), 1e-9),
             "moe_dropped": dropped}
    if routes:
        stats["routes"] = ids.reshape(b, s, k)
    return y.astype(dt).reshape(b, s, d), aux, stats
