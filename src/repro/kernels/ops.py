"""Public jit'd wrappers for the compression kernels.

Backend selection: ``pallas`` on TPU, ``ref`` (pure jnp, same math) on CPU,
``pallas_interpret`` for kernel-correctness tests. 64-bit payloads travel
as (hi, lo) uint32 pairs; float32/bfloat16 get bitcast convenience entry
points. ``compress_bits`` is the full jit'd encode pipeline (kernel ->
cumsum -> segment-sum packing) used by the speed benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import bitstream as bs
from . import bitpack_kernel, fpdelta_kernel, raster_kernel, ref

BLOCK_G = fpdelta_kernel.DEFAULT_BLOCK_G
BLOCK_N = raster_kernel.DEFAULT_BLOCK_N


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(backend: str | None) -> str:
    if backend in (None, "auto"):
        return default_backend()
    assert backend in ("pallas", "pallas_interpret", "ref"), backend
    return backend


def _pad_lanes(x: jnp.ndarray, mult: int, fill) -> jnp.ndarray:
    g = x.shape[-1]
    pad = (-g) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=fill)


def encode_groups_bits(pred_hi, pred_lo, son_hi, son_lo, *, zbits: int = 4,
                       width: int = 64, backend: str | None = None):
    """Residues + group nlz from (S, G) uint32 bit patterns.

    Pads G internally to the kernel block; returns unpadded (S, G) residues
    and (G,) nlz.
    """
    backend = _resolve(backend)
    s, g = son_hi.shape
    ph = _pad_lanes(jnp.asarray(pred_hi, jnp.uint32), BLOCK_G, 0)
    plo = _pad_lanes(jnp.asarray(pred_lo, jnp.uint32), BLOCK_G, 0)
    sh = _pad_lanes(jnp.asarray(son_hi, jnp.uint32), BLOCK_G, 0)
    slo = _pad_lanes(jnp.asarray(son_lo, jnp.uint32), BLOCK_G, 0)
    if backend == "ref":
        res_hi, res_lo, nlz = ref.group_residues_ref(ph, plo, sh, slo, zbits, width)
        nlz = nlz[None, :]
    else:
        res_hi, res_lo, nlz = fpdelta_kernel.encode_groups(
            ph, plo, sh, slo, zbits=zbits, width=width,
            interpret=(backend == "pallas_interpret"))
    return res_hi[:, :g], res_lo[:, :g], nlz[0, :g] if nlz.ndim == 2 else nlz[:g]


def decode_groups_bits(res_hi, res_lo, pred_hi, pred_lo, *,
                       backend: str | None = None):
    backend = _resolve(backend)
    s, g = res_hi.shape
    rh = _pad_lanes(jnp.asarray(res_hi, jnp.uint32), BLOCK_G, 0)
    rl = _pad_lanes(jnp.asarray(res_lo, jnp.uint32), BLOCK_G, 0)
    ph = _pad_lanes(jnp.asarray(pred_hi, jnp.uint32), BLOCK_G, 0)
    plo = _pad_lanes(jnp.asarray(pred_lo, jnp.uint32), BLOCK_G, 0)
    if backend == "ref":
        sh, slo = ref.decode_residues_ref(rh, rl, ph, plo)
    else:
        sh, slo = fpdelta_kernel.decode_groups(
            rh, rl, ph, plo, interpret=(backend == "pallas_interpret"))
    return sh[:, :g], slo[:, :g]


# --------------------------------------------------------- full pipelines

@functools.partial(jax.jit, static_argnames=("zbits", "width", "backend"))
def compress_bits(pred_hi, pred_lo, son_hi, son_lo, *, zbits: int = 4,
                  width: int = 64, backend: str = "ref"):
    """End-to-end jit'd encode: kernel -> pack codes & payload streams.

    Inputs (S, G) uint32 (G already padded to the kernel block by caller).
    Returns (code_words, payload_words, code_bits, payload_bits); the word
    arrays are sized at their static upper bounds, callers truncate with
    the bit counts.
    """
    s, g = son_hi.shape
    if backend == "ref":
        res_hi, res_lo, nlz = ref.group_residues_ref(
            pred_hi, pred_lo, son_hi, son_lo, zbits, width)
    else:
        res_hi, res_lo, nlz = fpdelta_kernel.encode_groups(
            pred_hi, pred_lo, son_hi, son_lo, zbits=zbits, width=width,
            interpret=(backend == "pallas_interpret"))
        nlz = nlz[0]
    if nlz.ndim == 2:
        nlz = nlz[0]
    code_words, code_bits = bs.pack_bits(
        nlz.astype(jnp.uint32), jnp.full((g,), zbits, jnp.int32),
        num_words=max(1, (g * zbits + 31) // 32))
    nbits = (width - nlz).astype(jnp.int32)
    if width == 64:
        # interleave (lo, hi) entries son-major: [lo00, hi00, lo10, hi10, ...]
        nb = jnp.repeat(nbits[None, :], s, axis=0)            # (S, G)
        lo_bits = jnp.minimum(nb, 32)
        hi_bits = jnp.maximum(nb - 32, 0)
        vals = jnp.stack([res_lo, res_hi], axis=1).reshape(2 * s, g)   # pairs per son
        lens = jnp.stack([lo_bits, hi_bits], axis=1).reshape(2 * s, g)
        # order: group-major then son-major then (lo,hi): transpose to (G, S*2)
        vals = vals.T.reshape(-1)
        lens = lens.T.reshape(-1)
        max_words = max(1, (g * s * 64 + 31) // 32)
    else:
        nb = jnp.minimum(jnp.repeat(nbits[None, :], s, axis=0), width)
        vals = res_lo.T.reshape(-1)
        lens = nb.T.reshape(-1)
        max_words = max(1, (g * s * width + 31) // 32)
    payload_words, payload_bits = bs.pack_bits(vals, lens, num_words=max_words)
    return code_words, payload_words, code_bits, payload_bits


# ------------------------------------------------------------- bitfields

def bitfield_pack(bits, *, backend: str | None = None) -> jnp.ndarray:
    """(N,) {0,1} -> ceil(N/32) uint32 words (bit i of word w = bits[32w+i])."""
    backend = _resolve(backend)
    bits = jnp.asarray(bits).astype(jnp.uint32).reshape(-1)
    n = bits.shape[0]
    pad = (-n) % (32 * bitpack_kernel.DEFAULT_BLOCK_W)
    bits = jnp.pad(bits, (0, pad))
    arr = bits.reshape(-1, 32).T  # (32, W)
    if backend == "ref":
        words = ref.bitpack_ref(arr)[None, :]
    else:
        words = bitpack_kernel.pack(arr, interpret=(backend == "pallas_interpret"))
    return words[0, : (n + 31) // 32]


def bitfield_unpack(words, n: int, *, backend: str | None = None) -> jnp.ndarray:
    backend = _resolve(backend)
    words = jnp.asarray(words, jnp.uint32).reshape(-1)
    pad = (-words.shape[0]) % bitpack_kernel.DEFAULT_BLOCK_W
    words = jnp.pad(words, (0, pad))[None, :]
    if backend == "ref":
        bits = ref.bitunpack_ref(words[0])
    else:
        bits = bitpack_kernel.unpack(words, interpret=(backend == "pallas_interpret"))
    return bits.T.reshape(-1)[:n]


# ------------------------------------------------------- AMR rasterization
#
# Device-reduction entry points (DESIGN.md §14): each takes flat BFS
# node arrays — coords (N, 3) int, levels (N,) int, values (N,) float32,
# ok (N,) bool (leaf ∧ owner ∧ not-padding) — plus the reducer params,
# and returns the reduced object with the host numpy reducers' exact
# geometry (``insitu.reducers``/``hercule.analysis``). ``resolution``
# must be a power of two (the integer pixel-geometry fast path;
# ``insitu.device`` falls back to host reducers otherwise). ``ref`` is
# the fast vectorized CPU path, ``pallas``/``pallas_interpret`` run the
# raster kernels.

def _axes_uv(axis: int) -> tuple[int, int]:
    ax_u, ax_v = (a for a in range(3) if a != axis)
    return ax_u, ax_v


def _pad_leaf(x, fill, block_n: int):
    return _pad_lanes(x[None, :], block_n, fill)


def _assert_pow2(resolution: int) -> None:
    if resolution <= 0 or resolution & (resolution - 1):
        raise ValueError(
            f"raster kernels need a power-of-two resolution, got "
            f"{resolution} (use the host reducer for arbitrary sizes)")


@functools.partial(jax.jit, static_argnames=(
    "axis", "position", "resolution", "n_levels", "backend", "block_n"))
def raster_slice(coords, levels, values, ok, *, axis: int, position: float,
                 resolution: int, n_levels: int, backend: str | None = None,
                 block_n: int = BLOCK_N):
    """Axis-aligned slice image (deepest covering leaf, NaN elsewhere)."""
    backend = _resolve(backend)
    _assert_pow2(resolution)
    ax_u, ax_v = _axes_uv(axis)
    coords2 = jnp.stack([coords[:, ax_u], coords[:, ax_v]], 1
                        ).astype(jnp.int32)
    levels = levels.astype(jnp.int32)
    if backend == "ref":
        return ref.slice_raster_ref(
            coords2, coords[:, axis], levels, values, ok,
            position=position, resolution=resolution, n_levels=n_levels)
    hit = raster_kernel.plane_hit(coords[:, axis], levels, position,
                                  n_levels)
    u0, v0, px = raster_kernel.leaf_table(coords2, levels,
                                          resolution=resolution)
    good = (ok & hit).astype(jnp.int32)
    return raster_kernel.slice_raster(
        _pad_leaf(u0, 0, block_n), _pad_leaf(v0, 0, block_n),
        _pad_leaf(px, 1, block_n), _pad_leaf(levels, 0, block_n),
        _pad_leaf(values, 0, block_n), _pad_leaf(good, 0, block_n),
        resolution=resolution, block_n=block_n,
        interpret=(backend == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=(
    "axis", "resolution", "n_levels", "backend", "block_n"))
def raster_projection(coords, levels, values, ok, *, axis: int,
                      resolution: int, n_levels: int,
                      backend: str | None = None, block_n: int = BLOCK_N):
    """Column density: per-leaf value · path length summed along ``axis``."""
    backend = _resolve(backend)
    _assert_pow2(resolution)
    ax_u, ax_v = _axes_uv(axis)
    coords2 = jnp.stack([coords[:, ax_u], coords[:, ax_v]], 1
                        ).astype(jnp.int32)
    levels = levels.astype(jnp.int32)
    if backend == "ref":
        return ref.projection_raster_ref(
            coords2, levels, values, ok, resolution=resolution,
            n_levels=n_levels)
    u0, v0, px = raster_kernel.leaf_table(coords2, levels,
                                          resolution=resolution)
    size = jnp.asarray(2.0, values.dtype) ** (-levels.astype(values.dtype))
    contrib = values * size          # the host reducer's v[sel] * size
    return raster_kernel.projection_raster(
        _pad_leaf(u0, 0, block_n), _pad_leaf(v0, 0, block_n),
        _pad_leaf(px, 1, block_n), _pad_leaf(contrib, 0, block_n),
        _pad_leaf(ok.astype(jnp.int32), 0, block_n),
        resolution=resolution, block_n=block_n,
        interpret=(backend == "pallas_interpret"))


# ------------------------------------------- partial (sharded/tiled) rasters
#
# Building blocks for the mesh path (``insitu.mesh_reduce``): rasterize
# an arbitrary BFS-ordered *subset* of the leaf table into a partial
# image — callers merge partials on-device (depth-resolve / ordered sum
# / psum). Unlike the full entry points these are not jitted here: they
# run inside the caller's ``shard_map``/jit. The histogram partial is
# the exception: jitted, it also serves the single-device path (the
# whole table is one subset). ``tile_n`` enables the
# tiled-gather formulation: the table is processed in fixed-size tiles
# gathered with ``dynamic_slice``, carrying the partial image between
# tiles — one compiled kernel at the tile shape serves any table length
# (bounded retraces) and the kernel working set stays at the padded
# bucket budget. Chaining tiles in BFS order is bit-identical to the
# single-shot call (see the carry kernels / seeded oracles).

def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _pad_rows(x, n_to: int, fill):
    n = x.shape[0]
    if n == n_to:
        return x
    width = [(0, n_to - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=fill)


def raster_slice_partial(coords, levels, values, ok, *, axis: int,
                         position: float, resolution: int, n_levels: int,
                         backend: str | None = None, block_n: int = BLOCK_N,
                         tile_n: int | None = None):
    """Partial slice raster: returns ``(image, depth)`` for a leaf subset.

    ``depth`` is the painting leaf's level (-1 where uncovered) — the
    on-device depth-resolve merge key. Seeding an all-NaN/-1 pair and
    running the full table reproduces :func:`raster_slice` bit for bit.
    """
    backend = _resolve(backend)
    _assert_pow2(resolution)
    ax_u, ax_v = _axes_uv(axis)
    coords2 = jnp.stack([coords[:, ax_u], coords[:, ax_v]], 1
                        ).astype(jnp.int32)
    c_axis = coords[:, axis]
    levels = levels.astype(jnp.int32)

    def tile(c2, ca, lv, val, okk, img, depth):
        if backend == "ref":
            return ref.slice_raster_depth_ref(
                c2, ca, lv, val, okk, position=position,
                resolution=resolution, n_levels=n_levels,
                init=(img, depth))
        hit = raster_kernel.plane_hit(ca, lv, position, n_levels)
        u0, v0, px = raster_kernel.leaf_table(c2, lv, resolution=resolution)
        good = (okk & hit).astype(jnp.int32)
        return raster_kernel.slice_raster_carry(
            _pad_leaf(u0, 0, block_n), _pad_leaf(v0, 0, block_n),
            _pad_leaf(px, 1, block_n), _pad_leaf(lv, 0, block_n),
            _pad_leaf(val, 0, block_n), _pad_leaf(good, 0, block_n),
            img, depth, resolution=resolution, block_n=block_n,
            interpret=(backend == "pallas_interpret"))

    seed = (jnp.full((resolution, resolution), jnp.nan, values.dtype),
            jnp.full((resolution, resolution), -1, jnp.int32))
    return _run_tiles(tile, (coords2, c_axis, levels, values, ok), seed,
                      tile_n=tile_n, block_n=block_n)


def raster_projection_partial(coords, levels, values, ok, *, axis: int,
                              resolution: int, n_levels: int,
                              backend: str | None = None,
                              block_n: int = BLOCK_N,
                              tile_n: int | None = None):
    """Partial projection raster: per-subset column-density image."""
    backend = _resolve(backend)
    _assert_pow2(resolution)
    ax_u, ax_v = _axes_uv(axis)
    coords2 = jnp.stack([coords[:, ax_u], coords[:, ax_v]], 1
                        ).astype(jnp.int32)
    levels = levels.astype(jnp.int32)

    def tile(c2, lv, val, okk, img):
        if backend == "ref":
            return (ref.projection_raster_ref(
                c2, lv, val, okk, resolution=resolution,
                n_levels=n_levels, init=img),)
        u0, v0, px = raster_kernel.leaf_table(c2, lv, resolution=resolution)
        size = jnp.asarray(2.0, val.dtype) ** (-lv.astype(val.dtype))
        contrib = val * size
        return (raster_kernel.projection_raster_carry(
            _pad_leaf(u0, 0, block_n), _pad_leaf(v0, 0, block_n),
            _pad_leaf(px, 1, block_n), _pad_leaf(contrib, 0, block_n),
            _pad_leaf(okk.astype(jnp.int32), 0, block_n),
            img, resolution=resolution, block_n=block_n,
            interpret=(backend == "pallas_interpret")),)

    seed = (jnp.zeros((resolution, resolution), values.dtype),)
    return _run_tiles(tile, (coords2, levels, values, ok), seed,
                      tile_n=tile_n, block_n=block_n)[0]


@functools.partial(jax.jit, static_argnames=("n_levels", "backend",
                                             "block_n"))
def raster_level_hist_partial(values, levels, ok, edges2, *, n_levels: int,
                              backend: str | None = None,
                              block_n: int = BLOCK_N):
    """Partial per-level histogram: (L, B) int32 counts for a subset.

    ``edges2`` is the (2, bins+1) :func:`raster_kernel.split_edges` form
    of the float64 bin edges. Integer counts are order-free, so partials merge with ``psum``. No
    ``tile_n``: the kernel's grid already streams the table block by
    block with an O(L·B) working set.
    """
    backend = _resolve(backend)
    levels = levels.astype(jnp.int32)
    if backend == "ref":
        return ref.level_hist_ref(values, levels, ok, edges2,
                                  n_levels=n_levels)
    return raster_kernel.level_hist(
        _pad_leaf(values, jnp.nan if values.dtype.kind == "f" else 0,
                  block_n),
        _pad_leaf(levels, 0, block_n),
        _pad_leaf(ok.astype(jnp.int32), 0, block_n),
        edges2, n_levels=n_levels, bins=edges2.shape[-1] - 1,
        block_n=block_n, interpret=(backend == "pallas_interpret"))


def _run_tiles(tile_fn, arrays, seed, *, tile_n: int | None, block_n: int):
    """Drive ``tile_fn`` over the table once, or tiled with a carry."""
    n = arrays[0].shape[0]
    if tile_n is None or n <= tile_n:
        return tile_fn(*arrays, *seed)
    if tile_n % block_n:
        raise ValueError(f"tile_n={tile_n} not a multiple of "
                         f"block_n={block_n}")
    tiles = -(-n // tile_n)
    padded = [_pad_rows(a, tiles * tile_n,
                        False if a.dtype == jnp.bool_ else 0)
              for a in arrays]

    def body(t, carry):
        cut = [jax.lax.dynamic_slice_in_dim(a, t * tile_n, tile_n, 0)
               for a in padded]
        return tuple(tile_fn(*cut, *carry))

    return jax.lax.fori_loop(0, tiles, body, tuple(seed))


# -------------------------------------------------------- f32 conveniences

def f32_bits(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)


def bits_f32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.uint32), jnp.float32)


def bf16_bits(x: jnp.ndarray) -> jnp.ndarray:
    u16 = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.bfloat16), jnp.uint16)
    return u16.astype(jnp.uint32)


def bits_bf16(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.uint32).astype(jnp.uint16), jnp.bfloat16)
