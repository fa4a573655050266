"""Model assembly: stacked-layer scan transformers for all families.

One ``LM`` class covers: dense/GQA decoders, MoE, SSM (mamba2), RG-LRU
hybrids (pattern-scan + unrolled tail), encoder-decoder (whisper-style,
frame-embedding stub), and VLM (patch-embedding prefix stub).

Layers are *stacked* (leading layer axis) and applied with ``lax.scan`` so
a 96-layer model compiles as one layer body + loop — essential for the
40-cell dry-run's compile times. ``cfg.remat`` wraps the scan body with
``jax.checkpoint`` for training memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import sharding
from . import attention, layers, moe, rglru, ssm
from .config import ModelConfig
from .layers import ParamSpec


def _stack_specs(spec, n: int):
    """Prepend a layer axis to every ParamSpec in a nested dict."""
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), (None, *s.axes), s.init, s.scale),
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))


def maybe_scan(body, carry, xs, *, unroll: bool):
    """lax.scan, or a Python unroll (for cost-analysis probe configs —
    XLA's cost analysis counts while-loop bodies once)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(y is None for y in ys):
        stacked = None
    else:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return carry, stacked


class LM:
    """A configured language model (pure functions over a param dict)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.kinds = cfg.layer_kinds()
        if cfg.block_pattern:
            pat = len(cfg.block_pattern)
            self.n_rep = cfg.n_layers // pat
            self.tail_kinds = self.kinds[self.n_rep * pat:]
        else:
            self.n_rep = cfg.n_layers
            self.tail_kinds = []

    # ------------------------------------------------------------- specs
    def _block_spec(self, kind: str) -> dict:
        cfg = self.cfg
        if kind == "attn":
            return {"ln1": layers.norm_spec(cfg),
                    "attn": attention.attn_spec(cfg),
                    "ln2": layers.norm_spec(cfg),
                    "mlp": layers.mlp_spec(cfg)}
        if kind == "moe":
            return {"ln1": layers.norm_spec(cfg),
                    "attn": attention.attn_spec(cfg),
                    "ln2": layers.norm_spec(cfg),
                    "moe": moe.moe_spec(cfg)}
        if kind == "ssm":
            return {"ln1": layers.norm_spec(cfg), "ssm": ssm.ssm_spec(cfg)}
        if kind == "rec":
            return {"ln1": layers.norm_spec(cfg),
                    "rec": rglru.rglru_spec(cfg),
                    "ln2": layers.norm_spec(cfg),
                    "mlp": layers.mlp_spec(cfg)}
        if kind == "xattn":  # enc-dec decoder block
            return {"ln1": layers.norm_spec(cfg),
                    "attn": attention.attn_spec(cfg),
                    "lnx": layers.norm_spec(cfg),
                    "xattn": attention.attn_spec(cfg, cross=True),
                    "ln2": layers.norm_spec(cfg),
                    "mlp": layers.mlp_spec(cfg)}
        raise ValueError(kind)

    def param_specs(self) -> dict:
        cfg = self.cfg
        spec: dict = {"embed": layers.embed_spec(cfg),
                      "final_norm": layers.norm_spec(cfg)}
        if cfg.block_pattern:
            block = {f"sub{i}_{k}": self._block_spec(k)
                     for i, k in enumerate(cfg.block_pattern)}
            spec["blocks"] = _stack_specs(block, self.n_rep)
            for i, k in enumerate(self.tail_kinds):
                spec[f"tail{i}"] = self._block_spec(k)
        elif cfg.family == "encdec":
            spec["enc"] = _stack_specs(self._block_spec("attn"), cfg.n_enc_layers)
            spec["blocks"] = _stack_specs(self._block_spec("xattn"), cfg.n_layers)
            spec["enc_norm"] = layers.norm_spec(cfg)
        else:
            kind = self.kinds[-1]
            k = cfg.first_k_dense
            if k:
                spec["dense"] = _stack_specs(self._block_spec("attn"), k)
            spec["blocks"] = _stack_specs(self._block_spec(kind),
                                          cfg.n_layers - k)
        return spec

    def param_axes(self):
        return layers.axes_tree(self.param_specs())

    def abstract_params(self):
        return layers.shapes_tree(self.param_specs(),
                                  jnp.dtype(self.cfg.param_dtype))

    def init(self, key):
        return layers.init_tree(self.param_specs(), key,
                                jnp.dtype(self.cfg.param_dtype))

    # ------------------------------------------------------------ blocks
    def _apply_block(self, kind: str, p, x, positions, *, enc_out=None,
                     enc_pos=None, window_override=None, routes=False):
        """One block: ``(x, aux loss, stats)``; ``stats`` holds an MoE
        layer's counters, else is empty."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        stats = {}
        if kind in ("attn", "moe", "xattn"):
            h = layers.apply_norm(p["ln1"], x, cfg)
            win = window_override if window_override is not None else cfg.window
            x = x + attention.multihead(
                p["attn"], h, cfg=self._cfg_with_window(win), positions=positions)
            if kind == "xattn":
                h = layers.apply_norm(p["lnx"], x, cfg)
                x = x + attention.multihead(
                    p["xattn"], h, cfg=cfg, positions=positions,
                    kv_x=enc_out, kv_positions=enc_pos, causal=False)
            h = layers.apply_norm(p["ln2"], x, cfg)
            if kind == "moe":
                y, aux, stats = moe.moe_layer(p["moe"], h, cfg, routes=routes)
                x = x + y
            else:
                x = x + layers.mlp(p["mlp"], h, cfg)
        elif kind == "ssm":
            h = layers.apply_norm(p["ln1"], x, cfg)
            y, _ = ssm.ssm_block(p["ssm"], h, cfg)
            x = x + y
        elif kind == "rec":
            h = layers.apply_norm(p["ln1"], x, cfg)
            y, _ = rglru.rglru_block(p["rec"], h, cfg)
            x = x + y
            h = layers.apply_norm(p["ln2"], x, cfg)
            x = x + layers.mlp(p["mlp"], h, cfg)
        else:
            raise ValueError(kind)
        x = sharding.constrain(x, "batch", "seq", "embed")
        return x, aux, stats

    @functools.lru_cache(maxsize=8)
    def _cfg_with_window(self, win):
        if win == self.cfg.window:
            return self.cfg
        import dataclasses
        return dataclasses.replace(self.cfg, window=win)

    # ----------------------------------------------------------- forward
    def forward(self, params, tokens, *, extras=None, return_cache=False):
        """Full-sequence forward -> ``(logits (B, S, V), aux)``.

        ``extras``: {"patch_embeds": (B,P,D)} for vlm, {"frames": (B,F,D)}
        for encdec.
        """
        return self.forward_stats(params, tokens, extras=extras)[:2]

    def forward_stats(self, params, tokens, *, extras=None, routes=False):
        """``forward`` plus ``stats``: each MoE layer's counters stacked
        on a leading layer axis (with ``routes``, its top-k expert ids)."""
        cfg = self.cfg
        extras = extras or {}
        b, s = tokens.shape
        x = layers.embed(params["embed"], tokens, cfg)
        if cfg.family == "vlm" and "patch_embeds" in extras:
            pe = extras["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, pe.shape[1]:, :]], axis=1)
        positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
        aux_total = jnp.zeros((), jnp.float32)

        enc_out = enc_pos = None
        if cfg.family == "encdec":
            enc_out, enc_pos = self._encode(params, extras["frames"])

        stats = {}
        if cfg.block_pattern:
            x, aux_total = self._hybrid_forward(params, x, positions)
        else:
            kind = "xattn" if cfg.family == "encdec" else self.kinds[-1]
            stacks = [("blocks", kind)]
            if cfg.first_k_dense:
                stacks.insert(0, ("dense", "attn"))
            for name, k in stacks:
                def body(carry, lp, k=k):
                    h, aux = carry
                    h, a, st = self._apply_block(k, lp, h, positions,
                                                 enc_out=enc_out,
                                                 enc_pos=enc_pos,
                                                 routes=routes)
                    return (h, aux + a), st
                if cfg.remat == "full":
                    body = jax.checkpoint(body)
                (x, aux_total), st = maybe_scan(body, (x, aux_total),
                                                params[name],
                                                unroll=cfg.unroll_layers)
                stats.update(st or {})
        x = layers.apply_norm(params["final_norm"], x, cfg)
        logits = layers.unembed(params["embed"], x, cfg)
        return (logits, aux_total, stats)

    def _encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.compute_dtype))
        b, f, _ = x.shape
        pos = jnp.arange(f, dtype=jnp.int32)[None, :].repeat(b, 0)

        def body(h, lp):
            h1 = layers.apply_norm(lp["ln1"], h, cfg)
            h = h + attention.multihead(lp["attn"], h1, cfg=cfg,
                                        positions=pos, causal=False)
            h2 = layers.apply_norm(lp["ln2"], h, cfg)
            h = h + layers.mlp(lp["mlp"], h2, cfg)
            return h, None
        if cfg.remat == "full":
            body = jax.checkpoint(body)
        x, _ = maybe_scan(body, x, params["enc"], unroll=cfg.unroll_layers)
        x = layers.apply_norm(params["enc_norm"], x, cfg)
        return x, pos

    def _hybrid_forward(self, params, x, positions):
        cfg = self.cfg
        pat = cfg.block_pattern
        aux = jnp.zeros((), jnp.float32)

        def body(carry, lp):
            h, a = carry
            for i, k in enumerate(pat):
                win = cfg.window if k == "attn" else None
                h, ai, _ = self._apply_block(k, lp[f"sub{i}_{k}"], h,
                                             positions, window_override=win)
                a = a + ai
            return (h, a), None
        if cfg.remat == "full":
            body = jax.checkpoint(body)
        (x, aux), _ = maybe_scan(body, (x, aux), params["blocks"],
                                 unroll=cfg.unroll_layers)
        for i, k in enumerate(self.tail_kinds):
            win = cfg.window if k == "attn" else None
            x, ai, _ = self._apply_block(k, params[f"tail{i}"], x,
                                         positions, window_override=win)
            aux = aux + ai
        return x, aux

    # ------------------------------------------------- loss (next token)
    def loss_fn(self, params, batch):
        """Mean next-token NLL plus ``aux_loss_alpha`` times the layers'
        summed balance loss; metrics add the MoE counters over layers."""
        logits, aux, stats = self.forward_stats(
            params, batch["tokens"],
            extras={k: v for k, v in batch.items()
                    if k in ("patch_embeds", "frames")})
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = jnp.sum((lse - ll) * mask) / jnp.clip(mask.sum(), 1.0)
        metrics = {"loss": nll, "aux": aux}
        if stats:
            metrics.update(
                moe_assignments_held=stats["moe_assignments_held"].sum(),
                moe_load_max_over_mean=stats["moe_load_max_over_mean"].max(),
                moe_dropped=stats["moe_dropped"].sum())
        return nll + self.cfg.aux_loss_alpha * aux, metrics

    def routes(self, params, tokens):
        """Each MoE layer's top-k expert ids, (L_moe, B, S, k)."""
        return self.forward_stats(params, tokens, routes=True)[2]["routes"]
