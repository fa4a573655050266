"""Float64 host reducers and the comparison that decides ``correct``.

The benchmark's own copy of the program's host reducers
(``insitu.reducers`` over ``hercule.analysis``): axis-aligned slice
(deepest covering leaf), column-density projection, per-level histogram
and level-of-detail cut, on the flat BFS arrays of one snapshot, in
float64 on the host. ``bench/tests/test_ref_parity.py`` checks them bit
for bit against the program's reducers.

A reducer is described by a plain spec (``{"kind": "slice", ...}``),
the same spec the driver turns into the program's reducer objects.
"""
from __future__ import annotations

import numpy as np


def _levels(arrays) -> np.ndarray:
    offsets = np.asarray(arrays["level_offsets"])
    out = np.zeros(np.asarray(arrays["refine"]).shape[0], np.int32)
    for lvl in range(len(offsets) - 1):
        out[offsets[lvl]:offsets[lvl + 1]] = lvl
    return out


def _n_levels(arrays) -> int:
    return int(np.asarray(arrays["level_offsets"]).shape[0] - 1)


def slice_image(arrays, field, axis, position, resolution):
    img = np.full((resolution, resolution), np.nan)
    levels = _levels(arrays)
    coords = np.asarray(arrays["coords"], np.int64)
    v = np.asarray(arrays[f"field:{field}"])
    leaves = np.flatnonzero(~np.asarray(arrays["refine"], bool))
    ax_u, ax_v = [a for a in range(3) if a != axis]
    for lvl in range(_n_levels(arrays)):
        sel = leaves[levels[leaves] == lvl]
        if sel.size == 0:
            continue
        size = 1.0 / (1 << lvl)
        lo_w = coords[sel, axis] * size
        sel = sel[(lo_w <= position) & (position < lo_w + size)]
        if sel.size == 0:
            continue
        c = coords[sel]
        u0 = np.floor(c[:, ax_u] * size * resolution).astype(int)
        v0 = np.floor(c[:, ax_v] * size * resolution).astype(int)
        px = max(1, int(round(size * resolution)))
        for i, node in enumerate(sel):
            img[u0[i]:u0[i] + px, v0[i]:v0[i] + px] = v[node]
    return {"image": img}


def projection_image(arrays, field, axis, resolution):
    img = np.zeros((resolution, resolution))
    levels = _levels(arrays)
    coords = np.asarray(arrays["coords"], np.int64)
    v = np.asarray(arrays[f"field:{field}"])
    leaves = np.flatnonzero(~np.asarray(arrays["refine"], bool))
    ax_u, ax_v = [a for a in range(3) if a != axis]
    for lvl in range(_n_levels(arrays)):
        sel = leaves[levels[leaves] == lvl]
        if sel.size == 0:
            continue
        size = 1.0 / (1 << lvl)
        c = coords[sel]
        u0 = np.floor(c[:, ax_u] * size * resolution).astype(int)
        v0 = np.floor(c[:, ax_v] * size * resolution).astype(int)
        contrib = v[sel] * size
        px = max(1, int(round(size * resolution)))
        if px == 1:
            np.add.at(img, (u0, v0), contrib)
        else:
            for i in range(sel.size):
                img[u0[i]:u0[i] + px, v0[i]:v0[i] + px] += contrib[i]
    return {"image": img}


def level_histogram(arrays, field, bins, max_levels=16):
    v = np.asarray(arrays[f"field:{field}"])
    leaf = ~np.asarray(arrays["refine"], bool)
    lo, hi = float(v[leaf].min()), float(v[leaf].max())
    if hi <= lo:
        hi = lo + 1.0
    edges = np.linspace(lo, hi, bins + 1)
    levels = _levels(arrays)
    hist = np.zeros((min(_n_levels(arrays), max_levels), bins), np.int64)
    for lvl in range(hist.shape[0]):
        sel = leaf & (levels == lvl)
        if sel.any():
            hist[lvl], _ = np.histogram(v[sel], bins=edges)
    return {"hist": hist, "edges": edges}


def lod_cut(arrays, max_level):
    """The tree truncated at ``max_level``: levels are a prefix of the
    BFS arrays, so the cut keeps that prefix and turns the refined nodes
    of level ``max_level`` into leaves."""
    offsets = np.asarray(arrays["level_offsets"], np.int64)
    if len(offsets) - 1 <= max_level + 1:
        return dict(arrays)
    n_keep = int(offsets[max_level + 1])
    refine = np.asarray(arrays["refine"], bool)[:n_keep].copy()
    refine[int(offsets[max_level]):] = False
    out = {"refine": refine, "level_offsets": offsets[:max_level + 2].copy()}
    for k, v in arrays.items():
        if k not in out:
            out[k] = np.asarray(v)[:n_keep].copy()
    return out


def run_dag(specs: list[dict], arrays: dict) -> list[dict]:
    """Each spec's output, in spec order; a ``source`` names an earlier
    spec's ``id`` whose output tree the reducer reads."""
    outs, by_id = [], {}
    for s in specs:
        src = by_id[s["source"]] if s.get("source") else arrays
        kind = s["kind"]
        if kind == "slice":
            out = slice_image(src, s["field"], s["axis"], s["position"],
                              s["resolution"])
        elif kind == "projection":
            out = projection_image(src, s["field"], s["axis"],
                                   s["resolution"])
        elif kind == "hist":
            out = level_histogram(src, s["field"], s["bins"])
        elif kind == "lod":
            out = lod_cut(src, s["max_level"])
        else:
            raise ValueError(f"unknown reducer kind {kind!r}")
        outs.append(out)
        if s.get("id"):
            by_id[s["id"]] = out
    return outs


# --------------------------------------------------------- comparison

def round_fields(arrays: dict, dtype) -> dict:
    """Every float array rounded to ``dtype`` and kept as float64."""
    return {k: (np.asarray(v).astype(dtype).astype(np.float64)
                if np.asarray(v).dtype.kind == "f" else v)
            for k, v in arrays.items()}


def compare(got: dict, want: dict) -> tuple[float, int]:
    """``(largest relative error, count of exact mismatches)``.

    Floats compare by relative error where the reference is non-zero;
    everything else must match exactly: keys, shapes, NaN positions,
    integer and boolean values and their dtypes, and floats where the
    reference is zero. Each exact mismatch counts one.
    """
    bad = len(set(got) ^ set(want))
    err = 0.0
    for k in set(got) & set(want):
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if g.shape != w.shape:
            bad += 1
            continue
        if w.dtype.kind != "f":
            bad += int(g.dtype != w.dtype)
            bad += int(np.count_nonzero(g != w))
            continue
        g, w = g.astype(np.float64), w.astype(np.float64)
        nan = np.isnan(w)
        bad += int(np.count_nonzero(nan != np.isnan(g)))
        ok = ~nan & ~np.isnan(g)
        diff, scale = np.abs(g[ok] - w[ok]), np.abs(w[ok])
        bad += int(np.count_nonzero(diff[scale == 0] > 0))
        rel = diff[scale > 0] / scale[scale > 0]
        if rel.size:
            err = max(err, float(rel.max()))
    return err, bad
