"""StableLM-2 decoder, its weights and AdamW, in plain ``jax.numpy``.

The benchmark's reference for the training cell, written from the
published architecture and the configuration file, importing nothing of
the program: pre-LayerNorm decoder blocks (eps from the config), rotary
position embedding over ``partial_rotary_factor`` of each head (the
two-halves rotation), causal softmax attention, a SiLU-gated MLP, final
LayerNorm, an untied LM head, and the mean next-token cross entropy.
AdamW with global-norm clipping and the warmup-stable-decay schedule
follows the config's ``optimizer`` block.

Weights are laid out as the program's parameter tree (layers stacked on
a leading axis) so the benchmark can hand one set, made here from the
seed, to both. ``compute`` names the precision: ``float32`` (every
product at ``highest``) is the reference; a lower type rounds the
residual stream and every matmul operand to it, which is the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the program's layout."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, n = d // h, cfg["num_hidden_layers"]
    norm = {"scale": (d,), "bias": (d,)}
    stacked = lambda t: {k: (n, *s) for k, s in t.items()}   # noqa: E731
    return {
        "embed": {"tok": (v, d), "unembed": (d, v)},
        "final_norm": dict(norm),
        "blocks": {
            "ln1": stacked(norm), "ln2": stacked(norm),
            "attn": stacked({"wq": (d, h, hd), "wk": (d, kv, hd),
                             "wv": (d, kv, hd), "wo": (h, hd, d)}),
            "mlp": stacked({"wi": (d, f), "wg": (d, f), "wo": (f, d)}),
        },
    }


def _fan_in(path: str, shape: tuple) -> int:
    if path.endswith("attn.wo"):
        return shape[-3] * shape[-2]
    return shape[-2] if path.startswith("blocks") else shape[0]


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, unit embedding rows,
    LayerNorm scales 1 and biases 0, all float32."""
    tree = shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = ".".join(p.key for p in path)
        if name.endswith(".scale"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name.endswith(".bias"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = 1.0 if name == "embed.tok" else \
                1.0 / math.sqrt(_fan_in(name, shape))
            out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ------------------------------------------------------------- forward

def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, positions, theta, rotary):
    """Rotate the first ``rotary`` channels of each head: pairs (i,
    i + rotary/2) turn by ``position * theta**(-2i/rotary)``."""
    rot, rest = x[..., :rotary], x[..., rotary:]
    half = rotary // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs      # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = rot[..., :half], rot[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([rot, rest], -1)


def loss(params: dict, tokens, labels, cfg: dict, compute=jnp.float32):
    """Mean next-token cross entropy over every label. Every product runs
    in float32 at ``highest`` precision; a lower ``compute`` type rounds
    the operands and the residual stream to it first."""
    r = (lambda a: a) if compute == jnp.float32 else \
        (lambda a: a.astype(compute).astype(jnp.float32))
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    rotary = int(round(hd * cfg["partial_rotary_factor"]))
    seq = tokens.shape[1]
    pos = jnp.arange(seq)
    causal = pos[None, :] <= pos[:, None]
    x = r(params["embed"]["tok"][tokens])
    b = params["blocks"]
    for l in range(cfg["num_hidden_layers"]):
        h = r(_layernorm(x, b["ln1"]["scale"][l], b["ln1"]["bias"][l], eps))
        q = mm("bsd,dhk->bshk", h, r(b["attn"]["wq"][l]))
        k = mm("bsd,dhk->bshk", h, r(b["attn"]["wk"][l]))
        v = mm("bsd,dhk->bshk", h, r(b["attn"]["wv"][l]))
        q = r(_rope(q, pos, cfg["rope_theta"], rotary))
        k = r(_rope(k, pos, cfg["rope_theta"], rotary))
        s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        p = r(jax.nn.softmax(s, axis=-1))
        o = r(mm("bhqk,bkhd->bqhd", p, r(v)))
        x = r(x + mm("bshk,hkd->bsd", o, r(b["attn"]["wo"][l])))
        h = r(_layernorm(x, b["ln2"]["scale"][l], b["ln2"]["bias"][l], eps))
        gate = mm("bsd,df->bsf", h, r(b["mlp"]["wg"][l]))
        up = mm("bsd,df->bsf", h, r(b["mlp"]["wi"][l]))
        m = r(jax.nn.silu(gate) * up)
        x = r(x + mm("bsf,fd->bsd", m, r(b["mlp"]["wo"][l])))
    x = r(_layernorm(x, params["final_norm"]["scale"],
                     params["final_norm"]["bias"], eps))
    logits = mm("bsd,dv->bsv", x, r(params["embed"]["unembed"]))
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ----------------------------------------------------------- optimizer

def learning_rate(step, opt: dict):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    t = (step - opt["warmup_steps"] - opt["stable_steps"]) \
        / max(opt["decay_steps"], 1)
    return opt["lr"] * warm * opt["min_lr_ratio"] ** jnp.clip(t, 0.0, 1.0)


def adamw(params, grads, mu, nu, step, opt: dict):
    """One AdamW update with global-norm clipping; ``step`` counts from
    1. Returns ``(params, mu, nu)``."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    b1, b2, lr = opt["b1"], opt["b2"], learning_rate(step, opt)
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) \
            + opt["weight_decay"] * p
        return p - lr * upd, m, v
    out = jax.tree.map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,      # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def make_step(cfg: dict, opt: dict, compute=jnp.float32):
    """Jitted ``(params, mu, nu, step, tokens, labels) -> (params, mu, nu,
    loss)`` with the three states donated."""
    def step_fn(params, mu, nu, step, tokens, labels):
        value, grads = jax.value_and_grad(loss)(params, tokens, labels,
                                                cfg, compute)
        params, mu, nu = adamw(params, grads, mu, nu, step, opt)
        return params, mu, nu, value
    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


# --------------------------------------------------------------- norms

def leaf_norms(tree: dict) -> dict[str, float]:
    """L2 norm of every leaf, stacked layers split into one leaf each
    (``blocks.attn.wq.2`` is layer 2's query projection)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = [".".join(p.key for p in path) for path, _ in flat]
    stacked = tuple(n.startswith("blocks.") for n in names)
    out = {}
    for name, st, n in zip(names, stacked,
                           _norms([leaf for _, leaf in flat], stacked)):
        if st:
            for l, nl in enumerate(n):
                out[f"{name}.{l}"] = float(nl)
        else:
            out[name] = float(n)
    return out


@functools.partial(jax.jit, static_argnums=1)
def _norms(leaves, stacked):
    def one(x, st):
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if st else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))
    return [one(x, st) for x, st in zip(leaves, stacked)]
