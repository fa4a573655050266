"""Pure-jnp oracles for the Pallas kernels (also the fast CPU path)."""
from __future__ import annotations

import jax.numpy as jnp

from .raster_kernel import edge_le, plane_hit, v_le_edge


def clz32_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros of uint32 via bit-smear + SWAR popcount.

    Identical to the kernel's formulation so both lower to the same ops on
    TPU (Mosaic has no native clz; jax.lax.clz is avoided on purpose).
    """
    x = x.astype(jnp.uint32)
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    # SWAR popcount
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    pop = (x * jnp.uint32(0x01010101)) >> 24
    return (jnp.uint32(32) - pop).astype(jnp.int32)


def group_residues_ref(pred_hi, pred_lo, son_hi, son_lo, zbits: int, width: int):
    """Oracle for the fpdelta encode kernel.

    Layout is (S, G): sons down the sublane axis, groups across lanes
    (TPU-native — see DESIGN.md §2). Returns res_hi, res_lo (S, G) and the
    clamped shared-leading-zero count nlz (G,).
    """
    res_hi = son_hi ^ pred_hi
    res_lo = son_lo ^ pred_lo
    m_hi = jnp.bitwise_or.reduce(res_hi, axis=0)
    m_lo = jnp.bitwise_or.reduce(res_lo, axis=0)
    if width == 64:
        nlz = jnp.where(m_hi != 0, clz32_ref(m_hi), 32 + clz32_ref(m_lo))
    elif width == 32:
        nlz = clz32_ref(m_lo)
    else:
        nlz = clz32_ref(m_lo) - 16
    nlz = jnp.minimum(nlz, (1 << zbits) - 1).astype(jnp.int32)
    return res_hi, res_lo, nlz


def decode_residues_ref(res_hi, res_lo, pred_hi, pred_lo):
    """Oracle for the fpdelta decode kernel (XOR with predictor)."""
    return res_hi ^ pred_hi, res_lo ^ pred_lo


def bitpack_ref(bits: jnp.ndarray) -> jnp.ndarray:
    """Pack a (32, W) {0,1} uint32 array into (W,) uint32 words (bit b of
    word w = bits[b, w]) — oracle for the bitpack kernel."""
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[:, None]
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=0,
                   dtype=jnp.uint32)


def bitunpack_ref(words: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`bitpack_ref`: (W,) uint32 -> (32, W) {0,1}."""
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    return ((words[None, :] >> shifts) & jnp.uint32(1)).astype(jnp.uint32)


# ------------------------------------------------------ raster oracles
#
# Fast CPU twins of raster_kernel.py: vectorized per-level scatters
# instead of the kernels' in-block leaf loop. Bit-identical to the host
# numpy reducers (and to the kernels) by construction: levels are
# processed ascending, per-pixel float updates keep the host's BFS leaf
# order (XLA CPU applies scatter updates sequentially, like np.add.at),
# coarse levels (cell rectangle >= 1 pixel) have unique cell->pixel
# maps so their scatter collapses to one update per pixel, and all
# pixel geometry is the same exact integer arithmetic. Invalid rows are
# dumped into a trailing trash slot instead of masked gathers.

def _level_pix(coords2, resolution: int, lvl: int):
    """Flat full-res pixel index of each node at one level (px == 1)."""
    k = resolution.bit_length() - 1
    u0 = coords2[:, 0] >> (lvl - k) if lvl > k else coords2[:, 0] << (k - lvl)
    v0 = coords2[:, 1] >> (lvl - k) if lvl > k else coords2[:, 1] << (k - lvl)
    return u0 * resolution + v0


def _slice_pyramid(coords2, c_axis, levels, values, ok, *,
                   position: float, resolution: int, n_levels: int):
    """One fused scatter of every node into a per-level pyramid buffer.

    The per-level formulation above runs ``n_levels`` full-table scatter
    passes (each O(N) sequential updates on CPU) — the dominant cost at
    512² on multi-million-node trees. Here every node computes its own
    (level-base + cell) target up front, so a *single* value scatter and
    a single painted-mask scatter cover all levels; XLA CPU applies the
    duplicate updates (fine levels, trash slot) in row order, preserving
    the BFS later-overrides semantics exactly. Returns the flat value
    buffer, painted buffer and the static per-level base offsets.
    """
    r = resolution
    k = r.bit_length() - 1
    bases, off = [], 0
    for lvl in range(n_levels):
        g = 1 << min(lvl, k)
        bases.append(off)
        off += g * g
    lvl32 = levels.astype(jnp.int32)
    sel = (ok & plane_hit(c_axis, lvl32, position, n_levels)
           & (lvl32 >= 0) & (lvl32 < n_levels))
    safe = jnp.clip(lvl32, 0, n_levels - 1)
    dn = jnp.maximum(safe - k, 0)
    g_l = jnp.int32(1) << jnp.minimum(safe, k)
    cell = ((coords2[:, 0].astype(jnp.int32) >> dn) * g_l
            + (coords2[:, 1].astype(jnp.int32) >> dn))
    base = jnp.asarray(bases, jnp.int32)[safe]
    idx = jnp.where(sel, base + cell, off)
    buf = jnp.full(off + 1, jnp.nan, values.dtype).at[idx].set(values)
    hit = jnp.zeros(off + 1, bool).at[idx].set(sel)
    return buf, hit, bases


def slice_raster_ref(coords2, c_axis, levels, values, ok, *,
                     position: float, resolution: int, n_levels: int):
    """Oracle for the slice kernel: deepest-covering-leaf painting.

    ``coords2`` is the (N, 2) in-plane coords, ``c_axis`` the (N,) coord
    along the slice axis. Resolution must be a power of two. Fused
    single-scatter formulation (see :func:`_slice_pyramid`); composing
    the pyramid coarse-to-fine with a painted-mask ``where`` reproduces
    the per-level ascending overrides bit for bit.
    """
    img, _ = slice_raster_depth_ref(
        coords2, c_axis, levels, values, ok, position=position,
        resolution=resolution, n_levels=n_levels)
    return img


def slice_raster_depth_ref(coords2, c_axis, levels, values, ok, *,
                           position: float, resolution: int, n_levels: int,
                           init=None):
    """Depth-tracking slice oracle, optionally seeded from ``init``.

    Returns ``(image, depth)`` where ``depth`` holds the painting leaf's
    level (-1 where unpainted) — the mesh path's depth-resolve merge and
    the tiled-gather carry both need it. ``init=(img0, depth0)`` seeds
    the paint: a level-``l`` candidate only lands where ``l >= depth0``,
    which is exactly the carry kernel's gate (within one level every
    candidate shares ``l``, so per-pixel gating is uniform and the
    last-set-in-BFS-order winner is unchanged).
    """
    r = resolution
    k = r.bit_length() - 1
    buf, hitbuf, bases = _slice_pyramid(
        coords2, c_axis, levels, values, ok, position=position,
        resolution=resolution, n_levels=n_levels)
    if init is None:
        img = jnp.full((r, r), jnp.nan, values.dtype)
        depth = jnp.full((r, r), -1, jnp.int32)
    else:
        img, depth = init
    for lvl in range(n_levels):
        g = 1 << min(lvl, k)
        px = r // g
        grid = buf[bases[lvl]:bases[lvl] + g * g].reshape(g, g)
        hit = hitbuf[bases[lvl]:bases[lvl] + g * g].reshape(g, g)
        up_val = jnp.repeat(jnp.repeat(grid, px, 0), px, 1)
        up_hit = jnp.repeat(jnp.repeat(hit, px, 0), px, 1)
        take = up_hit & (lvl >= depth)
        img = jnp.where(take, up_val, img)
        depth = jnp.where(take, jnp.int32(lvl), depth)
    return img, depth


def projection_raster_ref(coords2, levels, values, ok, *,
                          resolution: int, n_levels: int, init=None):
    """Oracle for the projection kernel: field * path-length column sum.

    Unlike the slice, a projection collapses one axis: several leaves
    of the *same* level can land on the same pixel (they differ along
    the projection axis), so per-pixel adds must run leaf by leaf in
    BFS order to match the host reducer's float accumulation. At coarse
    levels (cell rectangle >= 1 pixel) the scatter-add therefore
    targets a **coarse view** of the running image — exact, because all
    earlier (coarser) levels wrote values constant over this level's
    cells — and the result is replicated back; XLA CPU applies the
    scatter's duplicate updates in order, like ``np.add.at``.

    ``init`` seeds the accumulator (tiled-gather carry). The coarse
    view then requires the seed to be constant over the cells this
    pass actually *touches* — true for tile chaining, where the seed is
    the same rasterization's earlier-tile partial (BFS order ⇒ the seed
    holds only coarser-or-equal levels than any selected row); cells no
    selected row touches keep their pixels verbatim instead.
    """
    r = resolution
    k = r.bit_length() - 1
    img = jnp.zeros((r, r), values.dtype) if init is None else init
    zero = jnp.zeros((), values.dtype)
    for lvl in range(n_levels):
        sel = ok & (levels == lvl)
        contrib = values * jnp.asarray(1.0 / (1 << lvl), values.dtype)
        if lvl <= k:
            g, px = 1 << lvl, r >> lvl
            idx = jnp.where(sel, coords2[:, 0] * g + coords2[:, 1], g * g)
            flat = jnp.concatenate([img[::px, ::px].reshape(-1),
                                    jnp.zeros(1, values.dtype)])
            flat = flat.at[idx].add(jnp.where(sel, contrib, zero))
            # replicate only into cells some selected leaf touched: an
            # untouched cell keeps its running pixels verbatim, so a
            # carry seed holding *finer* levels than this pass (tile
            # chaining starts the level loop from 0 every tile) is never
            # flattened to its top-left subsample
            hit = jnp.zeros(g * g + 1, bool).at[idx].max(sel)
            up = jnp.repeat(jnp.repeat(flat[:-1].reshape(g, g), px, 0),
                            px, 1)
            uph = jnp.repeat(jnp.repeat(hit[:-1].reshape(g, g), px, 0),
                             px, 1)
            img = jnp.where(uph, up, img)
        else:
            idx = jnp.where(sel, _level_pix(coords2, r, lvl), r * r)
            flat = jnp.concatenate(
                [img.reshape(-1), jnp.zeros(1, values.dtype)])
            img = flat.at[idx].add(jnp.where(sel, contrib, zero)
                                   )[:-1].reshape(r, r)
    return img


def level_hist_ref(values, levels, ok, edges2, *, n_levels: int):
    """Oracle for the histogram kernel (np.histogram bin semantics).

    ``edges2`` is the :func:`raster_kernel.split_edges` pair, so float32
    values bin against the float64 edges exactly, as in the kernel.
    """
    hi, sgn = edges2[0], edges2[1]
    bins = hi.shape[-1] - 1
    ge = edge_le(hi[None, :], sgn[None, :], values[:, None])   # (N, B+1)
    b = jnp.minimum(jnp.sum(ge, axis=1, dtype=jnp.int32) - 1, bins - 1)
    good = (ok & ge[:, 0] & v_le_edge(hi[-1], sgn[-1], values)
            & (levels >= 0) & (levels < n_levels))
    flat = jnp.where(good, levels * bins + b, n_levels * bins)
    hist = jnp.zeros(n_levels * bins + 1, jnp.int32).at[flat].add(
        good.astype(jnp.int32))
    return hist[:-1].reshape(n_levels, bins)
