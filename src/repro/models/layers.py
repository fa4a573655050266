"""Shared layers: norms, MLPs, embeddings, RoPE, parameter specs.

Parameters are plain nested dicts built from ``ParamSpec`` tables so that
initialization, abstract shapes (dry-run) and logical sharding axes all
come from one source of truth.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import sharding


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple           # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones
    scale: float | None = None  # default: 1/sqrt(fan_in)

    def initializer(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(
            max(1, self.shape[0]))
        return (jax.random.normal(key, self.shape, jnp.float32) * scale).astype(dtype)


def init_tree(specs, key, dtype):
    """Instantiate a nested dict of ParamSpec -> arrays."""
    flat, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(flat))
    vals = [s.initializer(k, dtype) for s, k in zip(flat, keys)]
    return jax.tree.unflatten(treedef, vals)


def axes_tree(specs):
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def shapes_tree(specs, dtype):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def wcast(w, dtype, *axes):
    """Cast a sharded param to compute dtype, pinning the sharded layout.

    Without the constraint XLA may all-gather the f32 master weights and
    convert afterwards; pinning the bf16 copy to the same sharding makes
    the FSDP gather move half the bytes (§Perf i3)."""
    return sharding.constrain(w.astype(dtype), *axes)


# ------------------------------------------------------------------ norms

def rmsnorm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_spec(cfg) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones"),
                "bias": ParamSpec((cfg.d_model,), ("embed",), "zeros")}
    return {"scale": ParamSpec((cfg.d_model,), ("embed",), "zeros")}


def apply_norm(p, x, cfg):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ------------------------------------------------------------------- MLPs

def mlp_spec(cfg, d_in=None, d_ff=None) -> dict:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    gated = cfg.mlp_act in ("swiglu", "geglu")
    spec = {"wi": ParamSpec((d, f), ("fsdp", "mlp")),
            "wo": ParamSpec((f, d), ("mlp", "fsdp"))}
    if gated:
        spec["wg"] = ParamSpec((d, f), ("fsdp", "mlp"))
    return spec


def mlp(p, x, cfg):
    wi = wcast(p["wi"], x.dtype, "fsdp", "mlp")
    h = jnp.einsum("...d,df->...f", x, wi,
                   preferred_element_type=jnp.float32)
    if cfg.mlp_act in ("swiglu", "geglu"):
        wg = wcast(p["wg"], x.dtype, "fsdp", "mlp")
        g = jnp.einsum("...d,df->...f", x, wg,
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) if cfg.mlp_act == "swiglu" else jax.nn.gelu(g)) * h
    elif cfg.mlp_act == "relu2":          # nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    h = sharding.constrain(h.astype(x.dtype), "batch",
                           *(None,) * (x.ndim - 2), "mlp")
    wo = wcast(p["wo"], x.dtype, "mlp", "fsdp")
    # output projection accumulates partial sums ACROSS model ranks: emit
    # in compute dtype so the TP all-reduce moves bf16, not f32 (§Perf i6)
    return jnp.einsum("...f,fd->...d", h, wo)


# ------------------------------------------------------------- embeddings

def embed_spec(cfg) -> dict:
    spec = {"tok": ParamSpec((cfg.vocab_size, cfg.d_model),
                             ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        spec["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                    ("embed", "vocab"))
    return spec


def embed(p, tokens, cfg):
    x = jnp.take(p["tok"].astype(jnp.dtype(cfg.compute_dtype)), tokens, axis=0)
    return sharding.constrain(x, "batch", "seq", "embed")


def unembed(p, x, cfg):
    w = (p["tok"].T if cfg.tie_embeddings else p["unembed"])
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return sharding.constrain(logits, *("batch",) + (None,) * (x.ndim - 2) + ("vocab",))


# ------------------------------------------------------------------- RoPE

def rope(x, positions, theta: float, inv_freq=None, mscale: float = 1.0):
    """x: (..., S, H, hd); positions: (..., S) int. Rotates the halves
    ``(i, i + hd/2)`` by ``position * inv_freq[i]`` (default
    ``theta**(-2i/hd)``); ``mscale`` scales cos and sin (YaRN)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :] * mscale
    sin = jnp.sin(angles)[..., None, :] * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- YaRN
# DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` (arXiv:2309.00071)

def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """Per-pair inverse frequencies of the rotated channels: the original
    ``theta**(-2i/dim)`` for fast pairs, divided by the factor for slow
    ones, blended by a linear ramp between the correction dims."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / base ** pos
    inter = 1.0 / (cfg.yarn_factor * base ** pos)

    def corr_dim(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                                / (rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def yarn_cos_scale(cfg) -> float:
    """YaRN's scale of cos and sin: 1 when ``mscale == mscale_all_dim``."""
    return yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
        / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
