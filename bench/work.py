"""Operations and bytes that the benchmark's work needs, and the peaks.

The raster counts are the work the *algorithm* needs, computed from a
snapshot's per-level leaf counts, not from what a kernel implements:

* bytes: each leaf-table column the reducer reads, once per leaf, as the
  staged 32-bit type, plus the output written once;
* operations: one per output pixel each leaf's footprint covers,
  ``max(1, (R / 2**level)**2)``, over the leaves the reducer touches
  (a histogram leaf lands in one bin: one operation).

A kernel that passes over the whole image per leaf does far more than
this, so its share of the roofline reads low; a tiled kernel that paints
only the pixels each leaf covers raises it, and no implementation can
take it past 1.

:func:`lm_train_flops_per_token` counts the matrix multiplications of a
decoder's forward and backward pass per token, LM head included,
embedding lookup and recomputation excluded.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

#: bytes of one staged table entry (float32 values, int32 coords/levels)
ENTRY = 4

#: leaf-table columns each raster reads per leaf
COLUMNS = {"slice": 5,        # 3 coords (plane + 2 pixel axes), level, value
           "projection": 4,   # 2 pixel axes, level, value
           "hist": 2}         # level, value


def peaks(device_kind: str) -> dict:
    """The table's row for ``device_kind``; an unknown device raises."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def level_counts(arrays: dict) -> np.ndarray:
    """Leaves per level of one snapshot's BFS arrays."""
    offsets = np.asarray(arrays["level_offsets"])
    refine = np.asarray(arrays["refine"], bool)
    return np.array([int((~refine[offsets[l]:offsets[l + 1]]).sum())
                     for l in range(len(offsets) - 1)], np.int64)


def plane_counts(arrays: dict, axis: int, position: float) -> np.ndarray:
    """Leaves per level that an axis-aligned plane crosses."""
    offsets = np.asarray(arrays["level_offsets"])
    refine = np.asarray(arrays["refine"], bool)
    coords = np.asarray(arrays["coords"])
    out = []
    for l in range(len(offsets) - 1):
        a, b = int(offsets[l]), int(offsets[l + 1])
        cell = math.floor(position * (1 << l))
        out.append(int(((~refine[a:b]) & (coords[a:b, axis] == cell)).sum()))
    return np.array(out, np.int64)


def footprint(level: int, resolution: int) -> int:
    return max(1, (resolution >> level) ** 2) if level < 31 else 1


def raster_work(kind: str, arrays: dict, spec: dict) -> tuple[float, float]:
    """``(operations, bytes)`` one reducer call needs on ``arrays``."""
    leaves = level_counts(arrays)
    n = int(leaves.sum())
    if kind == "hist":
        levels = min(len(leaves), spec.get("max_levels", 16))
        return float(n), float(n * COLUMNS["hist"] * ENTRY
                               + levels * spec["bins"] * ENTRY)
    res = spec["resolution"]
    touched = plane_counts(arrays, spec["axis"], spec["position"]) \
        if kind == "slice" else leaves
    ops = sum(int(c) * footprint(l, res) for l, c in enumerate(touched))
    return float(ops), float(n * COLUMNS[kind] * ENTRY + res * res * ENTRY)


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward matmul FLOPs per token of a dense decoder.

    Per layer: the q, k, v and output projections, the gated MLP's three
    matrices, and causal attention's two score products over the
    ``(S + 1) / 2`` keys a query sees on average; then the LM head. The
    backward pass costs twice the forward.
    """
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    proj = d * hd * (heads + 2 * kv) + heads * hd * d
    mlp = 3 * d * f
    attn = 2 * heads * hd * (seq_len + 1) / 2
    fwd = 2 * cfg["num_hidden_layers"] * (proj + mlp + attn) \
        + 2 * d * cfg["vocab_size"]
    return 3.0 * fwd


#: HLO name of each raster kernel's call in a TPU trace, before its
#: ``.N`` suffix (the Pallas calls carry no ``name=``: these are the
#: custom calls' names as a v5e trace shows them, recorded in PERF.md)
KERNEL_OPS = {"slice": "slice_raster", "projection": "projection_raster",
              "hist": "level_hist"}


def kernel_of(op_name: str) -> str | None:
    """Which raster kernel a device op (``slice_raster.1``) belongs to."""
    base = op_name.rsplit(".", 1)[0] if op_name[-1:].isdigit() else op_name
    for kind, name in KERNEL_OPS.items():
        if base == name:
            return kind
    return None
