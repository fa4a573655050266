"""Tile-window raster kernels: bit parity with the ref twins and a painter.

The slice and projection kernels paint each leaf only into the (8, 128)
image tiles its rectangle covers, and walk only the rows whose ``ok`` is
set, by a block-relative next-painting-row column (DESIGN.md §14). Per
pixel the same leaves update it in the same order with the same
operation as a whole-image pass, so the results must be bit for bit
those of the ``ref`` twins (through ``ops``, BFS-ordered tables, whole
and tiled with a carry; dense, sparse and empty) and of a plain numpy
painter that walks the rows one by one (the kernels themselves, any row
order, any seed). Runs in the Pallas interpreter on the CPU.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import raster_kernel as rk

RESOLUTIONS = (8, 32, 256, 512)
POSITION = 0.5
N = 1536              # three 512-row blocks; the middle one paints nothing


def _k(resolution: int) -> int:
    return resolution.bit_length() - 1


def _bfs_table(seed: int, resolution: int, n: int = N):
    """Random BFS-ordered node arrays: levels 0..k+2, mixed ``ok``.

    Levels ascend (as in a BFS tree), so ``ref``'s per-level scatters see
    the kernel's accumulation order; leaves may overlap, which neither
    side assumes away. Half the rows sit on the slice plane so the slice
    paints at every level; ``ok`` is random with one all-zero block.
    """
    rng = np.random.default_rng(seed)
    n_levels = _k(resolution) + 3
    levels = np.sort(rng.integers(0, n_levels, n)).astype(np.int32)
    coords = np.floor(rng.random((n, 3)) * (1 << levels)[:, None]
                      ).astype(np.int32)
    cells = rk.plane_cells(POSITION, n_levels)
    on = rng.random(n) < 0.5
    coords[on, 2] = cells[levels[on]]
    values = rng.standard_normal(n).astype(np.float32) * 4.0 + 1.0
    ok = rng.random(n) < 0.7
    ok[ops.BLOCK_N:2 * ops.BLOCK_N] = False
    return (jnp.asarray(coords), jnp.asarray(levels), jnp.asarray(values),
            jnp.asarray(ok)), n_levels


def _assert_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(a, b, equal_nan=True)
    assert np.array_equal(np.isnan(a), np.isnan(b))


def _run(kind, backend, arrays, resolution, n_levels, tile_n):
    kw = dict(axis=2, resolution=resolution, n_levels=n_levels,
              backend=backend)
    if kind == "slice":
        return ops.raster_slice(*arrays, position=POSITION, **kw)
    if kind == "projection":
        return ops.raster_projection(*arrays, **kw)
    if kind == "slice_partial":
        return ops.raster_slice_partial(*arrays, position=POSITION,
                                        tile_n=tile_n, **kw)
    return ops.raster_projection_partial(*arrays, tile_n=tile_n, **kw)


OPS_CASES = [("slice", None), ("projection", None),
             ("slice_partial", None), ("slice_partial", 512),
             ("projection_partial", None), ("projection_partial", 512)]


@pytest.mark.parametrize("resolution", RESOLUTIONS)
@pytest.mark.parametrize("kind,tile_n", OPS_CASES)
def test_ops_pallas_equals_ref(kind, tile_n, resolution):
    """All four kernels through ``ops``: pallas_interpret == ref bitwise.

    ``tile_n=512`` chains three table tiles through the carry kernels,
    so each tile starts from the seed the one before it left.
    """
    arrays, n_levels = _bfs_table(resolution, resolution)
    got = {b: _run(kind, b, arrays, resolution, n_levels, tile_n)
           for b in ("pallas_interpret", "ref")}
    if kind == "slice_partial":
        for a, b in zip(got["pallas_interpret"], got["ref"]):
            _assert_bits(a, b)
    else:
        _assert_bits(got["pallas_interpret"], got["ref"])


def _sparse_table(kind: str, resolution: int, n: int = N):
    """A BFS table whose kernels walk few rows, or none.

    ``sparse``: ``ok`` on ~1% of rows, the first and last row of every
    block among them. ``few_hits``: ``ok`` on every row but the slice
    plane through six of them (the projection still paints them all).
    ``empty``: no ``ok`` row.
    """
    (coords, levels, values, ok), n_levels = _bfs_table(7 + resolution,
                                                        resolution, n)
    coords, levels = np.array(coords), np.maximum(np.array(levels), 1)
    rng = np.random.default_rng(resolution)
    cells = rk.plane_cells(POSITION, n_levels)
    if kind == "sparse":
        ok = rng.random(n) < 0.01
        ok[::ops.BLOCK_N] = ok[ops.BLOCK_N - 1::ops.BLOCK_N] = True
    elif kind == "few_hits":
        ok = np.ones(n, bool)
        coords[:, 2] = cells[levels] ^ 1        # a neighbour cell: a miss
        hits = rng.choice(n, 6, replace=False)
        coords[hits, 2] = cells[levels[hits]]
    else:
        ok = np.zeros(n, bool)
    return (jnp.asarray(coords), jnp.asarray(levels), values,
            jnp.asarray(ok)), n_levels


@pytest.mark.parametrize("table", ("sparse", "few_hits", "empty"))
@pytest.mark.parametrize("resolution", (32, 512))
@pytest.mark.parametrize("kind,tile_n", OPS_CASES)
def test_ops_pallas_equals_ref_sparse(kind, tile_n, resolution, table):
    """Few painting rows, or none: the walk skips the rest bit for bit.

    An empty table leaves the all-NaN slice (depth -1) and the all-zero
    projection.
    """
    arrays, n_levels = _sparse_table(table, resolution)
    got = {b: _run(kind, b, arrays, resolution, n_levels, tile_n)
           for b in ("pallas_interpret", "ref")}
    pairs = (zip(got["pallas_interpret"], got["ref"])
             if kind == "slice_partial"
             else [(got["pallas_interpret"], got["ref"])])
    for a, b in pairs:
        _assert_bits(a, b)
    if table == "empty":
        r = resolution
        want = {"slice": [np.full((r, r), np.nan, np.float32)],
                "slice_partial": [np.full((r, r), np.nan, np.float32),
                                  np.full((r, r), -1, np.int32)]}.get(
            kind, [np.zeros((r, r), np.float32)])
        outs = got["pallas_interpret"]
        for a, b in zip(outs if kind == "slice_partial" else [outs], want):
            _assert_bits(a, b)


# --------------------------------------------- the kernels against a painter

def _kernel_table(seed: int, resolution: int, n: int = N):
    """Leaf tables as the kernels read them, in no particular row order."""
    rng = np.random.default_rng(seed)
    k = _k(resolution)
    lvl = rng.integers(0, k + 3, n).astype(np.int32)
    coords = np.floor(rng.random((n, 2)) * (1 << lvl)[:, None]
                      ).astype(np.int32)
    u0, v0, px = (np.asarray(a) for a in rk.leaf_table(
        jnp.asarray(coords), jnp.asarray(lvl), resolution=resolution))
    val = rng.standard_normal(n).astype(np.float32)
    ok = (rng.random(n) < 0.5).astype(np.int32)
    ok[rk.DEFAULT_BLOCK_N:2 * rk.DEFAULT_BLOCK_N] = 0
    return u0, v0, px, lvl, val, ok


def _seed_images(seed: int, resolution: int):
    rng = np.random.default_rng(seed + 1)
    r = resolution
    img = rng.standard_normal((r, r)).astype(np.float32)
    img[rng.random((r, r)) < 0.3] = np.nan
    depth = rng.integers(-1, _k(r) + 3, (r, r)).astype(np.int32)
    return img, depth


def _paint_slice(u0, v0, px, lvl, val, ok, img, depth):
    """Row-by-row slice painter: deepest wins, equal level repaints."""
    img, depth = img.copy(), depth.copy()
    for i in np.flatnonzero(ok):
        win = np.s_[u0[i]:u0[i] + px[i], v0[i]:v0[i] + px[i]]
        take = lvl[i] >= depth[win]
        img[win] = np.where(take, val[i], img[win])
        depth[win] = np.where(take, lvl[i], depth[win])
    return img, depth


def _paint_projection(u0, v0, px, contrib, ok, img):
    """Row-by-row float32 accumulation over each leaf's rectangle."""
    img = img.copy()
    for i in np.flatnonzero(ok):
        win = np.s_[u0[i]:u0[i] + px[i], v0[i]:v0[i] + px[i]]
        img[win] = img[win] + contrib[i]
    return img


KERNELS = ("slice_raster", "slice_raster_carry", "projection_raster",
           "projection_raster_carry")


def _kernel_vs_painter(name, table, resolution, img0, depth0):
    u0, v0, px, lvl, val, ok = table
    rows = [jnp.asarray(a)[None, :] for a in table]
    kw = dict(resolution=resolution, interpret=True)
    if name.startswith("slice"):
        want = _paint_slice(u0, v0, px, lvl, val, ok, img0, depth0)
        if name == "slice_raster":
            _assert_bits(rk.slice_raster(*rows, **kw), want[0])
            return
        got = rk.slice_raster_carry(*rows, jnp.asarray(img0),
                                    jnp.asarray(depth0), **kw)
        for a, b in zip(got, want):
            _assert_bits(a, b)
        return
    rows = rows[:3] + rows[4:]             # (u0, v0, px, contrib, ok)
    want = _paint_projection(u0, v0, px, val, ok, img0)
    if name == "projection_raster":
        got = rk.projection_raster(*rows, **kw)
    else:
        got = rk.projection_raster_carry(*rows, jnp.asarray(img0), **kw)
    _assert_bits(got, want)


def _fresh(name, resolution, seed):
    """A carry kernel's random seed, or what the plain kernel starts from."""
    if name.endswith("_carry"):
        return _seed_images(seed, resolution)
    r = resolution
    fill = np.nan if name.startswith("slice") else 0.0
    return (np.full((r, r), fill, np.float32),
            np.full((r, r), -1, np.int32))


@pytest.mark.parametrize("resolution", RESOLUTIONS)
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_equals_row_painter(name, resolution):
    """Any row order, any carried seed: the kernel paints as the painter."""
    table = _kernel_table(100 + resolution, resolution)
    img0, depth0 = _fresh(name, resolution, resolution)
    _kernel_vs_painter(name, table, resolution, img0, depth0)


@pytest.mark.parametrize("px", (64, 128, 256))
@pytest.mark.parametrize("name", KERNELS)
def test_wide_leaves_cross_tiles(name, px):
    """Leaves wider than a tile at R=512 walk the multi-tile loop.

    Every row is a leaf of side ``px`` (8 to 64 tiles each), over a
    background of level-9/10 leaves, so a wide leaf meets pixels that
    finer rows painted before and after it.
    """
    r, n = 512, 1024
    rng = np.random.default_rng(px)
    lvl = np.where(rng.random(n) < 0.25, _k(r) - _k(px),
                   rng.integers(9, 11, n)).astype(np.int32)
    coords = np.floor(rng.random((n, 2)) * (1 << lvl)[:, None]
                      ).astype(np.int32)
    u0, v0, pxs = (np.asarray(a) for a in rk.leaf_table(
        jnp.asarray(coords), jnp.asarray(lvl), resolution=r))
    val = rng.standard_normal(n).astype(np.float32)
    ok = (rng.random(n) < 0.8).astype(np.int32)
    img0, depth0 = _fresh(name, r, px)
    _kernel_vs_painter(name, (u0, v0, pxs, lvl, val, ok), r, img0, depth0)


# ------------------------------------------------------- engagement counter

#                px   u0   v0   ok   tiles
FOOTPRINT_ROWS = [(64, 0, 0, 1),        # 8 row tiles x 1 lane block = 8
                  (64, 64, 192, 1),     # 8 x 1 = 8
                  (8, 8, 128, 1),       # one tile
                  (4, 12, 4, 1),        # inside one tile
                  (1, 511, 511, 1),     # one pixel, one tile
                  (2, 6, 126, 1),       # one 2x2 leaf in one tile
                  (128, 128, 256, 1),   # 16 x 1 = 16
                  (256, 256, 0, 1),     # 32 x 2 = 64
                  (64, 0, 0, 0),        # ok == 0: not counted
                  (1, 600, 0, 1)]       # outside the image: a row, no tile


def test_footprint_tiles_counts_rows_and_tiles():
    """``ok`` rows counted once; each rectangle's (8, 128) tiles exactly."""
    px, u0, v0, ok = (np.array(c, np.int32) for c in zip(*FOOTPRINT_ROWS))
    painting, tiles = rk.footprint_tiles(u0, v0, px, ok, 512)
    assert painting == 9
    assert tiles == 8 + 8 + 1 + 1 + 1 + 1 + 16 + 64
    # a 32x32 image is one (8, 32) tile per 8 rows
    assert rk.footprint_tiles(np.array([0, 8]), np.array([0, 16]),
                              np.array([32, 8]), np.array([1, 1]),
                              32) == (2, 4 + 1)


def test_walk_trips_equal_painting_rows():
    """The kernels' loop trips are the painting rows: 9 of 10, and none
    for the ``ok`` 0 row or the 502 padding rows of the block."""
    px, u0, v0, ok = (np.array(c, np.int32) for c in zip(*FOOTPRINT_ROWS))
    padded = ops._pad_leaf(jnp.asarray(ok), 0, rk.DEFAULT_BLOCK_N)
    walked = rk.walked_rows(rk.next_painting_rows(padded, rk.DEFAULT_BLOCK_N),
                            rk.DEFAULT_BLOCK_N)
    assert len(walked) == rk.footprint_tiles(u0, v0, px, ok, 512)[0] == 9
    np.testing.assert_array_equal(walked, np.flatnonzero(ok))
    assert 8 not in walked and walked.max() < len(ok)


# ------------------------------------------------ next-painting-row column

def _next_rows_np(ok, block_n: int) -> np.ndarray:
    """Per row, the next row of its block at or after it whose ``ok`` is
    set (``block_n`` if none), by a backward scan of each block."""
    ok = np.asarray(ok).reshape(-1, block_n) != 0
    out = np.empty(ok.shape, np.int32)
    for b, row in enumerate(ok):
        nxt = block_n
        for i in range(block_n - 1, -1, -1):
            nxt = i if row[i] else nxt
            out[b, i] = nxt
    return out.reshape(1, -1)


OK_PATTERNS = {                     # block_n = 8
    "all_zero": [0] * 8,
    "all_one": [1] * 8,
    "first_only": [1] + [0] * 7,
    "last_only": [0] * 7 + [1],
    "mixed": [0, 1, 1, 0, 0, 1, 0, 0],
    "padded": [1, 0, 1, 0, 0, 1, 0, 1, 1, 0, 1],   # 5 padding rows
}


@pytest.mark.parametrize("pattern", sorted(OK_PATTERNS))
def test_next_painting_rows_hand_built(pattern):
    """The column against a backward scan, and its walk against ``ok``."""
    ok = np.array(OK_PATTERNS[pattern], np.int32)
    padded = ops._pad_leaf(jnp.asarray(ok), 0, 8)
    nxt = np.asarray(rk.next_painting_rows(padded, 8))
    assert nxt.dtype == np.int32 and nxt.shape == padded.shape
    np.testing.assert_array_equal(nxt, _next_rows_np(padded, 8))
    np.testing.assert_array_equal(rk.walked_rows(nxt, 8),
                                  np.flatnonzero(ok))
    if pattern == "all_zero":
        assert (nxt == 8).all()
    if pattern == "all_one":
        np.testing.assert_array_equal(nxt[0], np.arange(8))
    if pattern == "padded":
        assert (nxt[0, len(ok):] == 8).all()


@pytest.mark.parametrize("block_n", (8, rk.DEFAULT_BLOCK_N))
def test_next_painting_rows_cut_at_tile_boundary(block_n):
    """Block-relative: the column of a two-block table, cut where a
    ``tile_n = block_n`` carry cuts it, is the column of each tile."""
    rng = np.random.default_rng(block_n)
    ok = (rng.random(2 * block_n) < 0.3).astype(np.int32)
    ok[block_n - 1] = 0                  # the first block ends bare
    ok[block_n] = 1                      # the second starts painting
    whole = np.asarray(rk.next_painting_rows(jnp.asarray(ok)[None], block_n))
    np.testing.assert_array_equal(whole, _next_rows_np(ok, block_n))
    for t in range(2):
        cut = jnp.asarray(ok[t * block_n:(t + 1) * block_n])[None]
        np.testing.assert_array_equal(
            np.asarray(rk.next_painting_rows(cut, block_n))[0],
            whole[0, t * block_n:(t + 1) * block_n])
    np.testing.assert_array_equal(rk.walked_rows(whole, block_n),
                                  np.flatnonzero(ok))
