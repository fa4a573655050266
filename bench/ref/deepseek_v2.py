"""DeepSeek-V2 decoder, its weights and AdamW, in plain ``jax.numpy``.

The benchmark's reference for the MoE training cell, written from the
published architecture (``modeling_deepseek.py`` of DeepSeek-V2-Lite) and
the configuration file, importing nothing of the program:

* pre-RMSNorm blocks (eps ``rms_norm_eps``); the norm's weight is
  ``1 + scale`` with the stored ``scale`` starting at 0 (the program's
  parameter layout, the same function);
* multi-head latent attention without a query LoRA: per-head queries of
  ``qk_nope + qk_rope`` channels; a KV latent of ``kv_lora_rank``
  (RMS-normed) up-projected to per-head ``k_nope`` and ``v``; one rotated
  key of ``qk_rope`` channels shared by all heads; causal softmax at
  ``(qk_nope + qk_rope)**-0.5 * mscale**2``;
* YaRN rotary frequencies (``rope_scaling``), rotating the two halves of
  the rotated channels (the published interleaved layout up to a fixed
  permutation of those weight columns);
* the first ``first_k_dense_replace`` layers dense (SwiGLU), the rest
  MoE: a float32 softmax router over ``router_experts`` outputs, greedy
  top-``num_experts_per_tok``, renormalised only with ``norm_topk_prob``,
  times ``routed_scaling_factor``; this chip's ``n_routed_experts`` held
  experts ``[expert_offset, expert_offset + n_routed_experts)`` add
  ``gate * SwiGLU_e(x)`` for the tokens that chose them, every token
  routed (no capacity); ``n_shared_experts`` always-on experts as one
  SwiGLU of their summed width;
* the sequence-wise balance loss (``seq_aux``) times ``aux_loss_alpha``,
  summed over MoE layers, plus the mean next-token cross entropy.

AdamW with global-norm clipping and the warmup-stable-decay schedule
follows the config's ``optimizer`` block (``ref/stablelm.py``'s).
A step runs the batch one sequence at a time and averages the losses:
both are per sequence, so this is the batch's loss and gradient up to
rounding. Each layer is rematerialised, and causal scores are computed
``QUERY_BLOCK`` rows at a time, so little more than one block of one
sequence's scores is live at once.

``compute`` names the precision: ``float32`` (every product at
``highest``) is the reference; a lower type rounds the residual stream
and every matmul operand to it (the router's stay float32, as
published), which is the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .stablelm import adamw, seed_key  # noqa: F401  (re-exported)

_EXPERT_LEAVES = ("wi", "wg", "wo")
#: rows of the causal score matrix computed at a time
QUERY_BLOCK = 512


def shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the program's layout."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    k = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - k
    e, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    f = cfg["intermediate_size"]
    norm = {"scale": (d,)}
    attn = {"wq": (d, h, nope + rd), "wkv_a": (d, r + rd),
            "kv_norm": {"scale": (r,)}, "wkv_b": (r, h, nope + vd),
            "wo": (h, vd, d)}

    def stacked(t, l):
        return jax.tree.map(lambda s: (l, *s), t,
                            is_leaf=lambda x: isinstance(x, tuple))
    moe = {"router": (d, cfg["router_experts"]), "wi": (e, d, fe),
           "wg": (e, d, fe), "wo": (e, fe, d),
           "shared": {"wi": (d, fs), "wg": (d, fs), "wo": (fs, d)}}
    tree = {"embed": {"tok": (v, d), "unembed": (d, v)},
            "final_norm": dict(norm),
            "blocks": stacked({"ln1": norm, "attn": attn, "ln2": norm,
                               "moe": moe}, n)}
    if k:
        tree["dense"] = stacked({"ln1": norm, "attn": attn, "ln2": norm,
                                 "mlp": {"wi": (d, f), "wg": (d, f),
                                         "wo": (f, d)}}, k)
    return tree


def _is_expert(name: str) -> bool:
    parts = name.split(".")
    return parts[-2] == "moe" and parts[-1] in _EXPERT_LEAVES


def _fan_in(name: str, shape: tuple) -> int:
    if name.startswith("embed"):
        return shape[0]
    if name.endswith("attn.wo"):
        return shape[1] * shape[2]
    return shape[2] if _is_expert(name) else shape[1]


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, unit embedding rows,
    norm scales 0 (weight 1), all float32."""
    tree = shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = ".".join(p.key for p in path)
        if name.endswith(".scale"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = 1.0 if name == "embed.tok" else \
                1.0 / math.sqrt(_fan_in(name, shape))
            out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------- rotary

def yarn(cfg: dict):
    """``(inv_freq, cos_sin_scale, softmax_scale)`` of the published
    ``DeepseekV2YarnRotaryEmbedding`` and attention."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor = float(rs["factor"])

    def get_mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    def corr_dim(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra_mask = 1.0 - ramp
    pos = np.arange(0, dim, 2) / dim
    extra = 1.0 / base ** pos
    inter = 1.0 / (factor * base ** pos)
    inv_freq = inter * (1 - extra_mask) + extra * extra_mask
    cs = get_mscale(rs["mscale"]) / get_mscale(rs["mscale_all_dim"])
    m = get_mscale(rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq.astype(np.float32), cs, scale


def _rope(x, positions, inv_freq, cs):
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[:, None, :] * cs, jnp.sin(ang)[:, None, :] * cs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ------------------------------------------------------------- forward

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + scale)


def _mm(*args):
    return jnp.einsum(*args, precision=jax.lax.Precision.HIGHEST)


def _rounder(compute):
    if compute == jnp.float32:
        return lambda a: a
    return lambda a: a.astype(compute).astype(jnp.float32)


def _attention(h, a, cfg, r):
    eps = cfg["rms_norm_eps"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    inv_freq, cs, scale = yarn(cfg)
    seq = h.shape[1]
    pos = jnp.arange(seq)
    q = _mm("bsd,dhk->bshk", h, r(a["wq"]))
    ckv = _mm("bsd,dr->bsr", h, r(a["wkv_a"]))
    lat = r(_rms(ckv[..., :rank], a["kv_norm"]["scale"], eps))
    kv = _mm("bsr,rhk->bshk", lat, r(a["wkv_b"]))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q[..., nope:], pos, inv_freq, cs)
    k_pe = _rope(ckv[..., None, rank:], pos, inv_freq, cs)
    q = r(jnp.concatenate([q[..., :nope], q_pe], -1))
    k = r(jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3],
                                         k_pe.shape[-1]))], -1))
    v = r(v)

    @jax.checkpoint
    def block(args):
        qb, pb = args
        s = _mm("bqhd,bkhd->bhqk", qb, k) * scale
        s = jnp.where(pos[None, :] <= pb[:, None], s, -jnp.inf)
        p = r(jax.nn.softmax(s, axis=-1))
        return r(_mm("bhqk,bkhd->bqhd", p, v))
    # query blocks of at most QUERY_BLOCK rows, each recomputed in the
    # backward pass: one block's scores are live at a time
    n = max(1, seq // QUERY_BLOCK) if seq % QUERY_BLOCK == 0 else 1
    qb = jnp.moveaxis(q.reshape(q.shape[0], n, seq // n, *q.shape[2:]), 1, 0)
    o = jax.lax.map(block, (qb, pos.reshape(n, seq // n)))
    o = jnp.moveaxis(o, 0, 1).reshape(*q.shape[:3], v.shape[-1])
    return _mm("bshk,hkd->bsd", o, r(a["wo"]))


def _swiglu(h, m, r):
    gate = _mm("bsd,df->bsf", h, r(m["wg"]))
    up = _mm("bsd,df->bsf", h, r(m["wi"]))
    return _mm("bsf,fd->bsd", r(jax.nn.silu(gate) * up), r(m["wo"]))


def route(h, router, cfg):
    """``(probs, gate, ids)``: the float32 softmax router over every
    routed expert and its greedy top-k."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, router), axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    return probs, gate * cfg["routed_scaling_factor"], ids


def seq_aux(probs, ids, n):
    """Per sequence ``sum_e (picks_e / (S*k/n)) * mean_s probs_e``,
    averaged over sequences."""
    b, s, k = ids.shape
    picks = jax.nn.one_hot(ids, n).sum(axis=(1, 2))            # (b, n)
    return jnp.mean(jnp.sum(picks / (s * k / n) * probs.mean(1), -1))


def _moe(h, m, cfg, r):
    probs, gate, ids = route(h, m["router"], cfg)
    held, off = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    # (b, s, held): each held expert's weight for each token, 0 unless chosen
    w = jnp.einsum("bsk,bske->bse", gate,
                   jax.nn.one_hot(ids - off, held))
    g = _mm("bsd,edf->bsef", h, r(m["wg"]))
    u = _mm("bsd,edf->bsef", h, r(m["wi"]))
    y = _mm("bsef,efd->bsed", r(jax.nn.silu(g) * u), r(m["wo"]))
    y = jnp.einsum("bsed,bse->bsd", y, w)
    if cfg["n_shared_experts"]:
        y = y + _swiglu(h, m["shared"], r)
    aux = seq_aux(probs, ids, cfg["router_experts"]) if cfg["seq_aux"] \
        else jnp.float32(0.0)
    return y, aux, ids


def _layer(x, p, cfg, r, kind):
    eps = cfg["rms_norm_eps"]
    h = r(_rms(x, p["ln1"]["scale"], eps))
    x = r(x + _attention(h, p["attn"], cfg, r))
    h = r(_rms(x, p["ln2"]["scale"], eps))
    if kind == "dense":
        return r(x + _swiglu(h, p["mlp"], r)), jnp.float32(0.0), None
    y, aux, ids = _moe(h, p["moe"], cfg, r)
    return r(x + y), aux, ids


def _forward(params, tokens, cfg, compute, remat=True):
    """``(logits, summed balance loss, top-k ids (L_moe, B, S, k))``; the
    layers of each stack run in a scan."""
    r = _rounder(compute)
    x = r(params["embed"]["tok"][tokens])
    aux, routes = jnp.float32(0.0), None
    for kind in ("dense", "blocks"):
        if kind not in params:
            continue
        fn = functools.partial(_layer, cfg=cfg, r=r, kind=kind)
        if remat:
            fn = jax.checkpoint(fn)

        def body(carry, p, fn=fn):
            x, a = carry
            x, al, ids = fn(x, p)
            return (x, a + al), ids
        (x, aux), ids = jax.lax.scan(body, (x, aux), params[kind])
        if ids is not None:
            routes = ids
    x = r(_rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"]))
    return _mm("bsd,dv->bsv", x, r(params["embed"]["unembed"])), aux, routes


def loss(params: dict, tokens, labels, cfg: dict, compute=jnp.float32):
    """Mean next-token cross entropy plus ``aux_loss_alpha`` times the
    MoE layers' summed sequence-wise balance loss."""
    logits, aux, _ = _forward(params, tokens, cfg, compute)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll) + cfg["aux_loss_alpha"] * aux


def logits(params, tokens, cfg, compute=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, cfg, compute, remat=False)[0]


def routes(params, tokens, cfg, compute=jnp.float32):
    """Top-k expert ids of each MoE layer, ``(L_moe, B, S, k)``."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, cfg, compute, remat=False)[2]


# ----------------------------------------------------------- optimizer

def batch_loss(params, tokens, labels, cfg, compute=jnp.float32):
    """The mean of :func:`loss` over the batch's sequences, taken one
    sequence at a time (each recomputed in the backward pass), so the
    gradient is accumulated in one buffer."""
    @jax.checkpoint
    def one(total, seq):
        return total + loss(params, seq[0][None], seq[1][None], cfg,
                            compute), None
    total, _ = jax.lax.scan(one, jnp.float32(0.0), (tokens, labels))
    return total / tokens.shape[0]


def grads(params, tokens, labels, cfg, compute=jnp.float32):
    """``(mean loss, its gradient)`` over the batch."""
    return jax.value_and_grad(batch_loss)(params, tokens, labels, cfg,
                                          compute)


def make_step(cfg: dict, opt: dict, compute=jnp.float32):
    """Jitted ``(params, mu, nu, step, tokens, labels) -> (params, mu, nu,
    loss)`` with the three states donated."""
    def step_fn(params, mu, nu, step, tokens, labels):
        with jax.default_matmul_precision("highest"):
            value, gr = grads(params, tokens, labels, cfg, compute)
            params, mu, nu = adamw(params, gr, mu, nu, step, opt)
        return params, mu, nu, value
    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


# --------------------------------------------------------------- norms

def leaf_norms(tree: dict) -> dict[str, float]:
    """L2 norm of every leaf; stacked layers split into one leaf each
    (``blocks.attn.wq.2``), and each held expert into its own
    (``blocks.moe.wi.2.5`` is layer 2's expert 5 of the held ones)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = [".".join(p.key for p in path) for path, _ in flat]
    split = tuple(0 if n.startswith("embed") or n.startswith("final")
                  else 2 if _is_expert(n) else 1 for n in names)
    out = {}
    for name, nsplit, n in zip(names, split,
                               _norms([leaf for _, leaf in flat], split)):
        n = np.asarray(n)
        if nsplit == 0:
            out[name] = float(n)
            continue
        for idx in np.ndindex(n.shape):
            out[".".join([name, *map(str, idx)])] = float(n[idx])
    return out


@functools.partial(jax.jit, static_argnums=1)
def _norms(leaves, split):
    def one(x, ns):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(ns, x.ndim))
                                if ns else None))
    return [one(x, ns) for x, ns in zip(leaves, split)]
