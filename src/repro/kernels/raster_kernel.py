"""Pallas TPU kernels for in-transit AMR rasterization (DESIGN.md §14).

The three hot reducers of the in-situ flow — axis-aligned slice,
projection (weighted axis sum) and per-level histogram — as on-device
kernels, so device-resident staging (``insitu.device``) transfers only
the *reduced* objects across the device→host boundary instead of the
full snapshot.

All three operate on a flat **leaf table** derived from the BFS tree
arrays (``ops.py`` builds it): per-leaf pixel origin ``(u0, v0)``,
rectangle size ``px``, level, value/contribution and a validity mask
(leaf ∧ owned ∧ slice-plane hit ∧ not padding). Pixel math is pure
integer arithmetic — the image resolution is required to be a power of
two, so ``u0 = c << (k-l)`` (or ``>> (l-k)``) and ``px = max(1, R >>
l)`` reproduce the host reducers' float ``floor``/``round`` results bit
for bit; non-pow2 resolutions take the host fallback in
``insitu.device``.

Kernel shape: the grid walks leaf blocks *sequentially* while the full
output image (or histogram) stays resident in VMEM across grid steps
(constant ``index_map``, initialized on the first step). Inside a block
the slice/projection kernels ``fori_loop`` over the table's rows,
reading each row's scalars from ``(1, BLOCK_N)`` tables in SMEM (vector
memory cannot serve a per-leaf dynamic lane index). A row whose ``ok``
is 0 (interior node, padding, unowned leaf, a leaf the slice plane
misses) does no vector work. A painting row visits only the image
tiles its rectangle covers: tiles of ``(min(8, R), min(128, R))``,
one vreg, from the row's own ``u0``, ``v0``, ``px``
(:func:`_tile_window`; a leaf of 8 px or less sits in one tile), each
updated through a rectangle mask built from a tile iota — masked
``where`` updates, never scatter. Each pixel is still updated by the
same leaves, in BFS row order, with the same operation as a pass over
the whole image, so per-pixel update *order* equals the host reducers'
BFS traversal and the results are bit for bit those of the whole-image
form. :func:`footprint_tiles` counts the rows that paint and the tiles
they visit, on the host. At R=512 the carry kernels' planes fit the
default scoped VMEM, so no ``vmem_limit_bytes`` is set. The histogram
kernel is fully vectorized: leaves ride the lane axis, a (B+1, BLOCK)
edge-compare against float64 edges split into float32 pairs
(:func:`split_edges`) reproduces ``np.searchsorted(edges, v,
"right")``, and an MXU
contraction of level and bin one-hots is the blocked scatter-add
(integer counts — order-free).

Tables are float32 (no float64 on the TPU): the kernels and the ``ref``
twins agree bit for bit on them, and against the float64 host reducers
they meet the DESIGN.md §18 tolerances (geometry, plane hits and bin
assignment exact; values carry float32 rounding).

Like the fpdelta kernels, every entry point takes ``interpret=`` so CPU
CI exercises the exact kernel path (``backend="pallas_interpret"`` in
``ops.py``); the pure-jnp twins in ``ref.py`` use vectorized per-level
scatters instead (fast CPU path) and are bit-identical by the same
ordering argument (XLA CPU applies scatter updates in order).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: leaves per grid step (lane-dim multiple of 128)
DEFAULT_BLOCK_N = 512


# ----------------------------------------------------------- leaf tables

def leaf_table(coords, levels, *, resolution: int):
    """Integer pixel geometry of every node: (u0, v0, px) per axis-pair.

    ``coords`` is the (N, 2) slice-plane projection of the node coords
    (caller drops the slice/projection axis); ``resolution`` must be a
    power of two (asserted by ops.py). Exact integer forms of the host
    reducers' ``floor(c * size * res)`` and ``round(size * res)``.
    """
    k = resolution.bit_length() - 1
    lvl = levels.astype(jnp.int32)
    up = jnp.maximum(k - lvl, 0)
    dn = jnp.maximum(lvl - k, 0)
    c = coords.astype(jnp.int32)
    u0 = (c[:, 0] << up) >> dn
    v0 = (c[:, 1] << up) >> dn
    px = jnp.maximum(resolution >> jnp.minimum(lvl, 30), 1).astype(jnp.int32)
    return u0, v0, px


def plane_cells(position: float, n_levels: int) -> np.ndarray:
    """Per level ``l``, the cell index the plane crosses: ``floor(pos·2^l)``.

    Host float64 and exact (scaling by ``2^l`` is exact), so
    ``c == cells[l]`` is the host slicer's ``c/2^l <= pos < (c+1)/2^l``
    bit for bit whatever dtype the device tables use. Clamped to
    ``[-1, 2^l]`` (no cell) so it fits int32.
    """
    return np.array([min(max(math.floor(position * (1 << lvl)), -1),
                         1 << lvl) for lvl in range(n_levels)], np.int32)


def plane_hit(coords_axis, levels, position: float, n_levels: int):
    """Host-exact slice-plane test as integer arithmetic (no float)."""
    cells = jnp.asarray(plane_cells(position, n_levels))
    lvl = jnp.clip(levels.astype(jnp.int32), 0, n_levels - 1)
    return coords_axis.astype(jnp.int32) == cells[lvl]


def split_edges(edges) -> np.ndarray:
    """(2, B+1) float32 form of float64 bin edges: rounding + residual sign.

    Row 0 is each edge rounded to float32, row 1 the sign of what the
    rounding dropped. For a float32 value ``v`` the pair decides
    ``edge <= v`` exactly (:func:`edge_le`), so float32 tables bin
    against the host reducer's float64 edges with no rounding of the
    edges themselves.
    """
    e = np.asarray(edges, np.float64)
    hi = e.astype(np.float32)
    sgn = np.sign(e - hi.astype(np.float64)).astype(np.float32)
    return np.stack([hi, sgn])


def edge_le(hi, sgn, v):
    """``edge <= v`` for float64 edges given as :func:`split_edges` rows."""
    return (hi < v) | ((hi == v) & (sgn <= 0))


def v_le_edge(hi, sgn, v):
    """``v <= edge`` for float64 edges given as :func:`split_edges` rows."""
    return (v < hi) | ((v == hi) & (sgn >= 0))


# ------------------------------------------------------------ slice kernel

def _smem_table(block_n: int):
    """Leaf-table block in scalar memory.

    The raster loops read one scalar per leaf at a dynamic index; vector
    memory only serves lane-aligned loads, so the per-leaf tables ride
    SMEM (one ``(1, block_n)`` block per grid step).
    """
    return pl.BlockSpec((1, block_n), lambda i: (0, i),
                        memory_space=pltpu.SMEM)


def _tile_shape(resolution: int) -> tuple[int, int]:
    """One (8, 128) vreg tile of the image, or the whole image if smaller."""
    return min(8, resolution), min(128, resolution)


def _tile_window(u0, v0, px, *, resolution: int, xp=jnp):
    """First tile origin and tile counts of a leaf's rectangle.

    Works on traced int32 scalars in the kernels (``xp=jnp``) and on
    numpy arrays in :func:`footprint_tiles` (``xp=np``). The rectangle
    is clipped to the image first, so a row outside it gets a zero count
    and touches no memory. For a leaf of the tree ``u0 = c << (k-l)`` is
    a multiple of ``px``, so a leaf with ``px <= 8`` lies in one tile
    row and one with ``px <= 128`` in one tile column.
    """
    th, tw = _tile_shape(resolution)
    sh, sw = th.bit_length() - 1, tw.bit_length() - 1
    u_lo, v_lo = xp.maximum(u0, 0), xp.maximum(v0, 0)
    u_hi = xp.minimum(u0 + px, resolution)
    v_hi = xp.minimum(v0 + px, resolution)
    r_lo, c_lo = (u_lo >> sh) << sh, (v_lo >> sw) << sw
    # tile sizes are powers of two: ceil-divide by shifting
    nr = xp.maximum((u_hi - r_lo + th - 1) >> sh, 0)
    nc = xp.maximum((v_hi - c_lo + tw - 1) >> sw, 0)
    return r_lo, c_lo, nr, nc


def _paint_tiles(u0, v0, px, update, *, resolution: int):
    """Call ``update(rows, cols, rect)`` on each tile of a leaf's rectangle.

    ``rows``/``cols`` are the tile's ``pl.ds`` slices and ``rect`` its
    (th, tw) in-rectangle mask. Tiles never overlap, so each pixel is
    updated once per leaf, as the whole-image pass did.
    """
    th, tw = _tile_shape(resolution)
    r_lo, c_lo, nr, nc = _tile_window(u0, v0, px, resolution=resolution)
    ti = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 1)

    def at(lo, n, size):
        # a tile as tall or wide as the image sits at 0: Mosaic wants a
        # static offset where the slice is narrower than 128 lanes
        return 0 if size == resolution else pl.multiple_of(lo + n * size,
                                                           size)

    def row(a, _):
        r = at(r_lo, a, th)

        def col(b, _):
            c = at(c_lo, b, tw)
            rows, cols = ti + r, tj + c
            rect = ((rows >= u0) & (rows < u0 + px)
                    & (cols >= v0) & (cols < v0 + px))
            update(pl.ds(r, th), pl.ds(c, tw), rect)
            return 0

        return jax.lax.fori_loop(0, nc, col, 0)

    jax.lax.fori_loop(0, nr, row, 0)


def footprint_tiles(u0, v0, px, ok, resolution: int) -> tuple[int, int]:
    """Rows that paint and tiles they paint, for a host-side leaf table.

    The raster kernels' own window arithmetic in numpy: a table's
    ``(rows_painting, tiles_painted)`` is how much of it the ``ok`` skip
    leaves and how many (8, 128) tile updates the painting rows make.
    ``ok`` is what the kernel reads (for the slice, leaf ∧ owner ∧
    plane hit).
    """
    sel = np.asarray(ok).reshape(-1) != 0
    u0, v0, px = (np.asarray(a, np.int64).reshape(-1)[sel]
                  for a in (u0, v0, px))
    _, _, nr, nc = _tile_window(u0, v0, px, resolution=resolution, xp=np)
    return int(sel.sum()), int((nr * nc).sum())


def _slice_body(u0_ref, v0_ref, px_ref, lvl_ref, val_ref, ok_ref,
                img_ref, depth_ref, *, block_n: int, resolution: int):
    def body(i, _):
        @pl.when(ok_ref[0, i] != 0)
        def _paint():
            lvl, val = lvl_ref[0, i], val_ref[0, i]

            def update(rows, cols, rect):
                # deepest leaf wins; equal level repaints (leaves arrive
                # in BFS order, so this is exactly the host painter's
                # later-overrides)
                depth = depth_ref[rows, cols]
                mask = rect & (lvl >= depth)
                img_ref[rows, cols] = jnp.where(mask, val,
                                                img_ref[rows, cols])
                depth_ref[rows, cols] = jnp.where(mask, lvl, depth)

            _paint_tiles(u0_ref[0, i], v0_ref[0, i], px_ref[0, i], update,
                         resolution=resolution)
        return 0

    jax.lax.fori_loop(0, block_n, body, 0)


def _slice_kernel(u0_ref, v0_ref, px_ref, lvl_ref, val_ref, ok_ref,
                  img_ref, depth_ref, *, block_n: int, resolution: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        img_ref[...] = jnp.full((resolution, resolution), jnp.nan,
                                img_ref.dtype)
        depth_ref[...] = jnp.full((resolution, resolution), -1, jnp.int32)

    _slice_body(u0_ref, v0_ref, px_ref, lvl_ref, val_ref, ok_ref,
                img_ref, depth_ref, block_n=block_n, resolution=resolution)


def _slice_carry_kernel(u0_ref, v0_ref, px_ref, lvl_ref, val_ref, ok_ref,
                        img0_ref, depth0_ref, img_ref, depth_ref, *,
                        block_n: int, resolution: int):
    """Slice kernel seeded from a carried (image, depth) pair.

    The seed is the partial result of earlier leaf-table tiles (the
    tiled-gather formulation) — semantically the kernel behaves as if
    the seed's leaves had been painted first, which they were.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        img_ref[...] = img0_ref[...]
        depth_ref[...] = depth0_ref[...]

    _slice_body(u0_ref, v0_ref, px_ref, lvl_ref, val_ref, ok_ref,
                img_ref, depth_ref, block_n=block_n, resolution=resolution)


@functools.partial(jax.jit, static_argnames=("resolution", "block_n",
                                             "interpret"))
def slice_raster(u0, v0, px, lvl, val, ok, *, resolution: int,
                 block_n: int = DEFAULT_BLOCK_N, interpret: bool = False):
    """Rasterize the slice from a padded (1, N) leaf table.

    ``ok`` already folds leaf/owner/plane-hit/padding; N must be a
    multiple of ``block_n`` (ops.py pads). Returns the (R, R) image
    (deepest-covering-leaf semantics, NaN where uncovered).
    """
    n = u0.shape[-1]
    assert n % block_n == 0, f"N={n} not padded to {block_n}"
    grid = (n // block_n,)
    tbl = _smem_table(block_n)
    out = pl.BlockSpec((resolution, resolution), lambda i: (0, 0))
    img, _ = pl.pallas_call(
        functools.partial(_slice_kernel, block_n=block_n,
                          resolution=resolution),
        grid=grid,
        in_specs=[tbl] * 6,
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((resolution, resolution), val.dtype),
            jax.ShapeDtypeStruct((resolution, resolution), jnp.int32),
        ],
        name="slice_raster",
        interpret=interpret,
    )(u0, v0, px, lvl, val, ok)
    return img


@functools.partial(jax.jit, static_argnames=("resolution", "block_n",
                                             "interpret"))
def slice_raster_carry(u0, v0, px, lvl, val, ok, img0, depth0, *,
                       resolution: int, block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool = False):
    """Seeded slice raster: paint one leaf-table tile over (img0, depth0).

    Returns the updated ``(image, depth)`` pair. Seeding with an all-NaN
    image and an all ``-1`` depth reproduces :func:`slice_raster` while
    also returning the depth buffer (the mesh path's depth-resolve merge
    needs it); chaining tiles in BFS order is bit-identical to one call
    over the concatenated table.
    """
    n = u0.shape[-1]
    assert n % block_n == 0, f"N={n} not padded to {block_n}"
    grid = (n // block_n,)
    tbl = _smem_table(block_n)
    out = pl.BlockSpec((resolution, resolution), lambda i: (0, 0))
    img, depth = pl.pallas_call(
        functools.partial(_slice_carry_kernel, block_n=block_n,
                          resolution=resolution),
        grid=grid,
        in_specs=[tbl] * 6 + [out, out],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((resolution, resolution), val.dtype),
            jax.ShapeDtypeStruct((resolution, resolution), jnp.int32),
        ],
        name="slice_raster_carry",
        interpret=interpret,
    )(u0, v0, px, lvl, val, ok, img0, depth0)
    return img, depth


# ------------------------------------------------------- projection kernel

def _proj_body(u0_ref, v0_ref, px_ref, contrib_ref, ok_ref, img_ref, *,
               block_n: int, resolution: int):
    def body(i, _):
        @pl.when(ok_ref[0, i] != 0)
        def _paint():
            contrib = contrib_ref[0, i]

            def update(rows, cols, rect):
                # where-guarded add: pixels outside the rectangle are
                # untouched (no +0.0), and per-pixel adds run in BFS leaf
                # order — the same float accumulation sequence as the
                # host reducer
                img = img_ref[rows, cols]
                img_ref[rows, cols] = jnp.where(rect, img + contrib, img)

            _paint_tiles(u0_ref[0, i], v0_ref[0, i], px_ref[0, i], update,
                         resolution=resolution)
        return 0

    jax.lax.fori_loop(0, block_n, body, 0)


def _proj_kernel(u0_ref, v0_ref, px_ref, contrib_ref, ok_ref, img_ref, *,
                 block_n: int, resolution: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        img_ref[...] = jnp.zeros((resolution, resolution), img_ref.dtype)

    _proj_body(u0_ref, v0_ref, px_ref, contrib_ref, ok_ref, img_ref,
               block_n=block_n, resolution=resolution)


def _proj_carry_kernel(u0_ref, v0_ref, px_ref, contrib_ref, ok_ref,
                       img0_ref, img_ref, *, block_n: int, resolution: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        img_ref[...] = img0_ref[...]

    _proj_body(u0_ref, v0_ref, px_ref, contrib_ref, ok_ref, img_ref,
               block_n=block_n, resolution=resolution)


@functools.partial(jax.jit, static_argnames=("resolution", "block_n",
                                             "interpret"))
def projection_raster(u0, v0, px, contrib, ok, *, resolution: int,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False):
    """Column-density accumulation from a padded (1, N) leaf table.

    ``contrib`` is the per-leaf field·path-length product (value ·
    2^-level, computed upstream so the multiply matches the host path).
    """
    n = u0.shape[-1]
    assert n % block_n == 0, f"N={n} not padded to {block_n}"
    grid = (n // block_n,)
    tbl = _smem_table(block_n)
    out = pl.BlockSpec((resolution, resolution), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_proj_kernel, block_n=block_n,
                          resolution=resolution),
        grid=grid,
        in_specs=[tbl] * 5,
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((resolution, resolution),
                                       contrib.dtype),
        name="projection_raster",
        interpret=interpret,
    )(u0, v0, px, contrib, ok)


@functools.partial(jax.jit, static_argnames=("resolution", "block_n",
                                             "interpret"))
def projection_raster_carry(u0, v0, px, contrib, ok, img0, *,
                            resolution: int, block_n: int = DEFAULT_BLOCK_N,
                            interpret: bool = False):
    """Seeded projection raster: accumulate one tile over ``img0``.

    Per-pixel adds still run in BFS leaf order, so chaining tiles in BFS
    order reproduces :func:`projection_raster` over the concatenated
    table bit for bit (same float accumulation sequence).
    """
    n = u0.shape[-1]
    assert n % block_n == 0, f"N={n} not padded to {block_n}"
    grid = (n // block_n,)
    tbl = _smem_table(block_n)
    out = pl.BlockSpec((resolution, resolution), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_proj_carry_kernel, block_n=block_n,
                          resolution=resolution),
        grid=grid,
        in_specs=[tbl] * 5 + [out],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((resolution, resolution),
                                       contrib.dtype),
        name="projection_raster_carry",
        interpret=interpret,
    )(u0, v0, px, contrib, ok, img0)


# -------------------------------------------------------- histogram kernel

def _hist_kernel(val_ref, lvl_ref, ok_ref, ehi_ref, esgn_ref, hist_ref, *,
                 n_levels: int, bins: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros(hist_ref.shape, jnp.int32)

    v = val_ref[...]                        # (1, BLOCK) leaves on lanes
    lvl = lvl_ref[...]
    ehi, esgn = ehi_ref[...], esgn_ref[...]     # (bins + 1, 1) columns
    # searchsorted(edges, v, side="right") == #edges <= v, as an
    # edge-compare reduction over sublanes (no in-kernel gather)
    ge = edge_le(ehi, esgn, v).astype(jnp.int32)          # (B+1, BLOCK)
    idx = jnp.sum(ge, axis=0, keepdims=True) - 1
    b = jnp.minimum(idx, bins - 1)                 # top edge inclusive
    good = ((ok_ref[...] != 0) & (ge[0:1, :] != 0)
            & v_le_edge(ehi[bins:, :], esgn[bins:, :], v)
            & (lvl >= 0) & (lvl < n_levels))
    # blocked scatter-add as one MXU contraction over the leaf axis:
    # (L, BLOCK) level one-hot x (B, BLOCK) bin one-hot -> (L, B) counts;
    # 0/1 operands and <= BLOCK-term sums are exact in bf16 x f32
    lp, bp = hist_ref.shape
    lv_hot = ((jax.lax.broadcasted_iota(jnp.int32, (lp, v.shape[1]), 0)
               == lvl) & good).astype(jnp.bfloat16)
    b_hot = (jax.lax.broadcasted_iota(jnp.int32, (bp, v.shape[1]), 0)
             == b).astype(jnp.bfloat16)
    counts = jax.lax.dot_general(lv_hot, b_hot, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    hist_ref[...] = hist_ref[...] + counts.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_levels", "bins", "block_n",
                                             "interpret"))
def level_hist(val, lvl, ok, edges2, *, n_levels: int, bins: int,
               block_n: int = DEFAULT_BLOCK_N, interpret: bool = False):
    """(L, B) per-level histogram via blocked one-hot contraction.

    ``edges2`` is the (2, B+1) :func:`split_edges` form of the float64
    edges. Bin assignment reproduces ``np.histogram(v, bins=edges)``
    exactly (right-open bins, top edge inclusive, out-of-range
    excluded); integer counts make accumulation order-free. The
    accumulator is padded to whole (8, 128) tiles and cut on return.
    """
    n = val.shape[-1]
    assert n % block_n == 0, f"N={n} not padded to {block_n}"
    grid = (n // block_n,)
    tbl = pl.BlockSpec((1, block_n), lambda i: (0, i))
    col = pl.BlockSpec((bins + 1, 1), lambda i: (0, 0))
    lp, bp = -(-n_levels // 8) * 8, -(-bins // 128) * 128
    hist = pl.pallas_call(
        functools.partial(_hist_kernel, n_levels=n_levels, bins=bins),
        grid=grid,
        in_specs=[tbl, tbl, tbl, col, col],
        out_specs=pl.BlockSpec((lp, bp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((lp, bp), jnp.int32),
        name="level_hist",
        interpret=interpret,
    )(val, lvl, ok, edges2[0][:, None], edges2[1][:, None])
    return hist[:n_levels, :bins]
