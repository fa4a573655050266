"""Attention: GQA/MQA, RoPE, sliding window, chunked (flash-style) scan,
cross-attention, and single-token decode against a KV cache.

Long sequences never materialize the full S x S score matrix: queries are
processed in ``cfg.attn_chunk`` blocks inside a ``lax.scan`` (block scores
live only inside one scan step — the TPU-friendly stand-in for a fused
flash kernel; the quadratic FLOPs stay visible to ``cost_analysis``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import sharding
from . import layers
from .layers import ParamSpec


def attn_spec(cfg, cross: bool = False) -> dict:
    if cfg.kv_lora_rank:
        return mla_spec(cfg)
    d, hd, nh, nkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": ParamSpec((d, nh, hd), ("fsdp", "heads", "head_dim")),
        "wk": ParamSpec((d, nkv, hd), ("fsdp", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, nkv, hd), ("fsdp", "kv_heads", "head_dim")),
        "wo": ParamSpec((nh, hd, d), ("heads", "head_dim", "fsdp")),
    }


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _mask_bias(q_pos, k_pos, window):
    """(…, Sq, Sk) additive mask: causal + optional sliding window."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale=None):
    """q/k: (B,Sq,H,hd) and (B,Sk,H,hd), v: (B,Sk,H,hv); bias: (Sq,Sk) or
    None; ``scale`` defaults to hd**-0.5."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def multihead(p, x, *, cfg, positions, kv_x=None, kv_positions=None,
              causal=True, return_kv=False):
    """Full attention over a sequence (training / prefill / cross).

    x: (B, S, D). kv_x (cross-attention source) defaults to x.
    With ``return_kv`` also returns the (pre-GQA-repeat, post-RoPE)
    (B, S, nkv, hd) K/V for cache seeding at prefill.
    """
    if cfg.kv_lora_rank:
        if not causal or kv_x is not None or return_kv:
            raise NotImplementedError("MLA runs causal self-attention only")
        return mla(p, x, cfg=cfg, positions=positions)
    dt = x.dtype
    wq = layers.wcast(p["wq"], dt, "fsdp", "heads", "head_dim")
    wk = layers.wcast(p["wk"], dt, "fsdp", "kv_heads", "head_dim")
    wv = layers.wcast(p["wv"], dt, "fsdp", "kv_heads", "head_dim")
    q = jnp.einsum("bsd,dhk->bshk", x, wq,
                   preferred_element_type=jnp.float32).astype(dt)
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dhk->bshk", src, wk,
                   preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsd,dhk->bshk", src, wv,
                   preferred_element_type=jnp.float32).astype(dt)
    kpos = positions if kv_positions is None else kv_positions
    if causal:  # cross-attention skips RoPE on purpose (whisper-style)
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, kpos, cfg.rope_theta)
    q = sharding.constrain(q, "batch", "seq", "heads", "head_dim")
    k = sharding.constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = sharding.constrain(v, "batch", "seq", "kv_heads", "head_dim")
    kv_raw = (k, v)
    k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)

    if not causal:
        out = _sdpa(q, k, v, None)
    else:
        out = _causal(q, k, v, positions, kpos, cfg)
    out = sharding.constrain(out, "batch", "seq", "heads", "head_dim")
    wo = layers.wcast(p["wo"], dt, "heads", "head_dim", "fsdp")
    # bf16 output so the TP all-reduce moves half the bytes (§Perf i6)
    out = jnp.einsum("bshk,hkd->bsd", out, wo)
    return (out, kv_raw) if return_kv else out


def _causal(q, k, v, positions, kpos, cfg, scale=None, remat=False):
    """Causal (optionally windowed) attention; flash-style scan over
    ``cfg.attn_chunk`` query blocks, full KV per block, past one block.
    ``remat`` recomputes each block's scores in the backward pass instead
    of keeping every block's (B, H, chunk, S) scores and probabilities."""
    b, s = q.shape[:2]
    pos1 = positions[0] if positions.ndim > 1 else positions
    kpos1 = kpos[0] if kpos.ndim > 1 else kpos
    if s <= cfg.attn_chunk:
        return _sdpa(q, k, v, _mask_bias(pos1, kpos1, cfg.window), scale)
    nblk = s // cfg.attn_chunk
    assert s % cfg.attn_chunk == 0, (s, cfg.attn_chunk)
    qb = q.reshape(b, nblk, cfg.attn_chunk, *q.shape[2:])
    pb = pos1.reshape(nblk, cfg.attn_chunk)

    def step(_, inp):
        qi, pi = inp
        bias = _mask_bias(pi, kpos1, cfg.window)
        return None, _sdpa(qi, k, v, bias, scale)
    if remat:
        step = jax.checkpoint(step)
    _, ob = jax.lax.scan(step, None, (jnp.moveaxis(qb, 1, 0), pb))
    return jnp.moveaxis(ob, 0, 1).reshape(b, s, *ob.shape[3:])


# ------------------------------------------------ multi-head latent (MLA)

def mla_spec(cfg) -> dict:
    """DeepSeek-V2 attention without a query LoRA: per-head queries of
    ``nope + rope`` channels; one shared KV latent (RMS-normed) of
    ``kv_lora_rank``, up-projected to per-head ``k_nope`` and ``v``; one
    rotated key of ``rope`` channels shared by every head."""
    d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq": ParamSpec((d, nh, qk), ("fsdp", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, r + cfg.qk_rope_head_dim), ("fsdp", None)),
        "kv_norm": {"scale": ParamSpec((r,), (None,), "zeros")},
        "wkv_b": ParamSpec((r, nh, cfg.qk_nope_head_dim + cfg.v_head_dim),
                           (None, "heads", "head_dim")),
        "wo": ParamSpec((nh, cfg.v_head_dim, d), ("heads", "head_dim", "fsdp")),
    }


def mla_softmax_scale(cfg) -> float:
    """``(nope + rope)**-0.5``, times YaRN's ``mscale_all_dim`` gain
    squared when YaRN is on."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor:
        m = layers.yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def mla(p, x, *, cfg, positions):
    """Causal MLA over a sequence. x: (B, S, D) -> (B, S, D). The rotated
    channels use the half-split rotation (the published interleaved one
    up to a fixed permutation of those weight columns)."""
    dt = x.dtype
    nope, rd, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    f32 = jnp.float32
    if cfg.yarn_factor:
        inv_freq, cs = layers.yarn_inv_freq(cfg), layers.yarn_cos_scale(cfg)
    else:
        inv_freq, cs = None, 1.0
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt),
                       preferred_element_type=f32).astype(dt)
        ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt),
                         preferred_element_type=f32).astype(dt)
        latent = layers.rmsnorm(ckv[..., :r], p["kv_norm"]["scale"])
        kv = jnp.einsum("bsr,rhk->bshk", latent, p["wkv_b"].astype(dt),
                        preferred_element_type=f32).astype(dt)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = layers.rope(q[..., nope:], positions, cfg.rope_theta,
                           inv_freq, cs)
        k_pe = layers.rope(ckv[..., None, r:], positions, cfg.rope_theta,
                           inv_freq, cs)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], rd))], -1)
        out = _causal(q, k, v, positions, positions, cfg,
                      mla_softmax_scale(cfg), remat=cfg.remat == "full")
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


# ------------------------------------------------------------------ decode

def decode_kv(p, x, *, cfg, cache_k, cache_v, pos):
    """One-token attention against a KV cache.

    x: (B, 1, D); cache_k/v: (B, S_cache, nkv, hd); pos: () current index
    (ring-buffer slot = pos % S_cache when cfg.window is set).
    Returns (out (B,1,D), new_k, new_v).
    """
    b = x.shape[0]
    dt = x.dtype
    s_cache = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    posv = jnp.full((b, 1), pos, jnp.int32)
    q = layers.rope(q, posv, cfg.rope_theta)
    k_new = layers.rope(k_new, posv, cfg.rope_theta)
    slot = pos % s_cache if cfg.window is not None else pos
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, slot, axis=1)
    cache_k = sharding.constrain(cache_k, "batch", "kv_seq", "kv_heads", "head_dim")
    cache_v = sharding.constrain(cache_v, "batch", "kv_seq", "kv_heads", "head_dim")

    # grouped-query attention WITHOUT materializing the GQA repeat: the
    # repeat reshards the seq-sharded cache to head-sharded, which GSPMD
    # realizes as a full f32 KV all-gather (1 GB/layer measured on
    # internlm2 decode_32k, §Perf i9). Keeping the kv dim in the einsum
    # leaves the cache seq-sharded; only the tiny softmax partials and the
    # (B,1,H,hd) output cross shards.
    n_rep = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, 1, cfg.n_kv_heads, n_rep, q.shape[-1])
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    kidx = jnp.arange(s_cache)
    if cfg.window is not None:
        # ring buffer: slot j holds the token written `(slot - j) % W` steps
        # ago; valid iff that age is within the number of tokens seen so far
        age = (slot - kidx) % s_cache
        valid = age < jnp.minimum(pos + 1, s_cache)
    else:
        valid = kidx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)   # (b,h,r,1,S)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, cache_v,
                     preferred_element_type=jnp.float32).astype(dt)
    out = out.reshape(b, 1, cfg.n_heads, q.shape[-1])
    wo = layers.wcast(p["wo"], dt, "heads", "head_dim", "fsdp")
    out = jnp.einsum("bshk,hkd->bsd", out, wo)
    return out, cache_k, cache_v


def decode_cross(p, x, *, cfg, enc_k, enc_v):
    """One-token cross-attention against precomputed encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = _repeat_kv(enc_k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(enc_v, cfg.n_heads // cfg.n_kv_heads)
    out = _sdpa(q, k.astype(dt), v.astype(dt), None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)
