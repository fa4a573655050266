"""The turbulent-cloud AMR snapshot generator, the benchmark's own.

The hydro part is a copy of the program's ``sim.fields.orion`` (lognormal
density from multi-octave periodic value noise, plus velocity and
pressure) and of ``sim.amrgen.generate_tree`` (RAMSES-like level-by-level
refinement on a density threshold, leaf fields at cell centres, intensive
restriction of coarse cells); ``bench/tests/test_ref_parity.py`` checks
that it agrees bit for bit with the program's generator. The density
noise is evaluated once per node and reused for the refinement test, the
leaf density and the pressure, which gives the same bits as evaluating
it three times.

A RAMSES MHD cell also carries the face-centred magnetic field, the
three components on the cell's left faces and on its right faces
(:data:`B_NAMES`). They come from :func:`magnetic_field`: a mean field
along x plus seeded divergence-free Fourier modes, evaluated at each
leaf's face centres. The snapshot is returned as the flat BFS arrays
that ``AMRTree.to_arrays`` gives, with the eleven fields in RAMSES's
output order.

:func:`variants` derives the window's snapshots from one base tree: the
same structure, the fields scaled by seeded factors.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: threads of the generator: numpy releases the GIL inside its loops
WORKERS = min(8, os.cpu_count() or 1)

CHILD_OFFSETS = np.array(
    [[i & 1, (i >> 1) & 1, (i >> 2) & 1] for i in range(8)], np.int64)

B_NAMES = ("B_x_left", "B_y_left", "B_z_left",
           "B_x_right", "B_y_right", "B_z_right")
FIELD_NAMES = ("density", "velocity_x", "velocity_y", "velocity_z", *B_NAMES,
               "pressure")


class ValueNoise:
    """Periodic multi-octave trilinear value noise on the unit box."""

    def __init__(self, seed: int, octaves: int = 6, base_res: int = 4,
                 persistence: float = 0.62):
        rng = np.random.default_rng(seed)
        self.grids = []
        self.persistence = persistence
        res = base_res
        for _ in range(octaves):
            self.grids.append(rng.standard_normal((res, res, res)))
            res *= 2

    def __call__(self, pts: np.ndarray) -> np.ndarray:
        return by_rows(self._eval, pts)

    def _eval(self, pts: np.ndarray) -> np.ndarray:
        # the program's arithmetic, with the eight corner gathers made on
        # the flattened grid: the same values
        out = np.zeros(pts.shape[0])
        amp = 1.0
        for g in self.grids:
            n = g.shape[0]
            flat = g.ravel()
            x = pts * n
            lo = np.floor(x)
            f = x - lo
            i0 = lo.astype(np.int64) % n
            i1 = (i0 + 1) % n
            x0, x1 = i0[:, 0] * (n * n), i1[:, 0] * (n * n)
            y0, y1 = i0[:, 1] * n, i1[:, 1] * n
            z0, z1 = i0[:, 2], i1[:, 2]
            c000 = flat[x0 + y0 + z0]
            c100 = flat[x1 + y0 + z0]
            c010 = flat[x0 + y1 + z0]
            c110 = flat[x1 + y1 + z0]
            c001 = flat[x0 + y0 + z1]
            c101 = flat[x1 + y0 + z1]
            c011 = flat[x0 + y1 + z1]
            c111 = flat[x1 + y1 + z1]
            fx, fy, fz = f[:, 0], f[:, 1], f[:, 2]
            c00 = c000 * (1 - fx) + c100 * fx
            c10 = c010 * (1 - fx) + c110 * fx
            c01 = c001 * (1 - fx) + c101 * fx
            c11 = c011 * (1 - fx) + c111 * fx
            c0 = c00 * (1 - fy) + c10 * fy
            c1 = c01 * (1 - fy) + c11 * fy
            out += amp * (c0 * (1 - fz) + c1 * fz)
            amp *= self.persistence
        return out


def by_rows(fn, *arrays, axis: int = 0):
    """``fn`` over blocks of rows of ``arrays`` in :data:`WORKERS`
    threads, the blocks' results joined along ``axis``. ``fn`` must treat
    each row on its own, so the result does not depend on the blocks."""
    n = arrays[0].shape[0]
    cuts = np.linspace(0, n, WORKERS + 1).astype(np.int64)
    blocks = [tuple(a[lo:hi] for a in arrays)
              for lo, hi in zip(cuts[:-1], cuts[1:]) if hi > lo]
    if len(blocks) <= 1:
        return fn(*arrays)
    with ThreadPoolExecutor(len(blocks)) as pool:
        parts = list(pool.map(lambda b: fn(*b), blocks))
    return np.concatenate(parts, axis=axis)


def orion_fields(realization: int, sigma: float = 1.6) -> dict:
    """name -> evaluator over (N, 3) unit-box points."""
    s = ValueNoise(realization)
    vxn = ValueNoise(realization + 1, octaves=5)
    vyn = ValueNoise(realization + 2, octaves=5)
    vzn = ValueNoise(realization + 3, octaves=5)

    def density(pts):
        return np.exp(sigma * s(pts))

    return {"density": density,
            "velocity_x": lambda p: 0.8 * vxn(p),
            "velocity_y": lambda p: 0.8 * vyn(p),
            "velocity_z": lambda p: 0.8 * vzn(p),
            "pressure": lambda p: density(p) ** (5.0 / 3.0)}


def _levels(offsets: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros(n, np.int32)
    for lvl in range(len(offsets) - 1):
        out[offsets[lvl]:offsets[lvl + 1]] = lvl
    return out


def _child_start(refine: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    out = np.full(refine.shape[0], -1, np.int64)
    for lvl in range(len(offsets) - 2):
        a, b = int(offsets[lvl]), int(offsets[lvl + 1])
        ref = refine[a:b]
        rank = np.cumsum(ref) - ref
        idx = np.flatnonzero(ref) + a
        out[idx] = offsets[lvl + 1] + 8 * rank[ref]
    return out


def magnetic_field(coords: np.ndarray, levels: np.ndarray, cfg: dict,
                   realization: int) -> dict[str, np.ndarray]:
    """Face-centred magnetic field of the cells ``coords`` at ``levels``.

    ``B = b_mean x + sum_m a_m cos(2 pi k_m . x + phi_m)`` with integer
    wavenumbers ``1 <= |k_m|_inf <= b_kmax`` (periodic on the unit box),
    ``a_m`` perpendicular to ``k_m`` (so ``div B = 0``) and ``|a_m|``
    proportional to ``|k_m|^(-11/6)`` (a Kolmogorov spectrum), scaled so
    that the modes' mean square is ``b_turb**2``. Component ``j`` is
    taken at the centres of the cell's two faces normal to axis ``j``.
    """
    rng = np.random.default_rng([realization, 4])
    kmax, n_modes = cfg["b_kmax"], cfg["b_modes"]
    k = np.zeros((0, 3), np.int64)
    while k.shape[0] < n_modes:
        cand = rng.integers(-kmax, kmax + 1, size=(n_modes, 3))
        k = np.concatenate([k, cand[np.abs(cand).max(axis=1) > 0]])
    k = k[:n_modes]
    kn = np.linalg.norm(k, axis=1)
    a = np.cross(k, rng.standard_normal((n_modes, 3)))
    a *= (kn ** (-11.0 / 6.0) / np.linalg.norm(a, axis=1))[:, None]
    a *= cfg["b_turb"] / np.sqrt(0.5 * (a ** 2).sum())
    phase = rng.uniform(0.0, 2.0 * np.pi, n_modes)

    def faces(c: np.ndarray, lvl: int) -> np.ndarray:
        """Rows 0-2 left faces, 3-5 right faces, of cells ``c`` at ``lvl``."""
        out = np.zeros((6, c.shape[0]))
        out[0] += cfg["b_mean"]
        out[3] += cfg["b_mean"]
        centers = (c + 0.5) / (1 << lvl)
        for m in range(n_modes):
            theta = 2.0 * np.pi * (centers @ k[m]) + phase[m]
            cos, sin = np.cos(theta), np.sin(theta)
            for j in range(3):
                d = np.pi * k[m, j] / (1 << lvl)          # pi k dx
                cd, sd = a[m, j] * np.cos(d), a[m, j] * np.sin(d)
                out[j] += cos * cd + sin * sd           # a cos(theta - d)
                out[3 + j] += cos * cd - sin * sd       # a cos(theta + d)
        return out

    # cells come level by level (BFS order): one face offset per run
    bounds = [0, *(np.flatnonzero(np.diff(levels)) + 1), levels.shape[0]]
    parts = [by_rows(lambda c, lvl=int(levels[lo]): faces(c, lvl),
                     coords[lo:hi], axis=1)
             for lo, hi in zip(bounds[:-1], bounds[1:])]
    both = np.concatenate(parts, axis=1)
    left, right = both[:3], both[3:]
    return dict(zip(B_NAMES, [*left, *right]))


def generate(cfg: dict, jitter_seed: int) -> dict[str, np.ndarray]:
    """One snapshot's BFS arrays (``refine``, ``owner``, ``level_offsets``,
    ``coords``, ``field:<name>``) for a configuration's generator keys."""
    realization, sigma = cfg["realization"], cfg.get("sigma", 1.6)
    fields = orion_fields(realization, sigma)
    noise = ValueNoise(realization)
    min_level, max_level = cfg["min_level"], cfg["max_level"]
    threshold, factor = cfg["threshold"], cfg["level_factor"]
    jitter = cfg["refine_jitter"]
    rng = np.random.default_rng(jitter_seed)
    level_coords = [np.zeros((1, 3), np.int64)]
    level_refine, level_noise = [], []
    for lvl in range(max_level):
        coords = level_coords[lvl]
        n = coords.shape[0]
        if lvl < min_level:
            ref = np.ones(n, bool)
            level_noise.append(None)
        else:
            centers = (coords + 0.5) / (1 << lvl)
            level_noise.append(noise(centers))
            vals = np.exp(sigma * level_noise[lvl])
            thr = threshold * factor ** (lvl - min_level)
            if jitter:
                thr = thr * np.exp(jitter * rng.standard_normal(n))
            ref = vals > thr
        level_refine.append(ref)
        kids = (2 * coords[ref][:, None, :] + CHILD_OFFSETS[None, :, :])
        level_coords.append(kids.reshape(-1, 3))
        if not ref.any():
            level_coords = level_coords[:lvl + 2]
            break
    level_refine.append(np.zeros(level_coords[-1].shape[0], bool))

    refine = np.concatenate(level_refine).astype(bool)
    coords = np.concatenate(level_coords)
    offsets = np.zeros(len(level_coords) + 1, np.int64)
    for i, c in enumerate(level_coords):
        offsets[i + 1] = offsets[i] + c.shape[0]
    n = refine.shape[0]
    levels = _levels(offsets, n)
    centers = (coords + 0.5) / (1 << levels.astype(np.int64))[:, None]
    leaves = ~refine
    # the density noise of every node, from the refinement where it ran
    s_all = np.zeros(n)
    for lvl in range(len(level_coords)):
        a, b = int(offsets[lvl]), int(offsets[lvl + 1])
        got = level_noise[lvl] if lvl < len(level_noise) else None
        s_all[a:b] = got if got is not None else noise(centers[a:b])
    density = np.exp(sigma * s_all[leaves])
    at_leaves = {"density": density, "pressure": density ** (5.0 / 3.0)}
    for name in ("velocity_x", "velocity_y", "velocity_z"):
        at_leaves[name] = fields[name](centers[leaves])
    at_leaves.update(magnetic_field(coords[leaves], levels[leaves], cfg,
                                    realization))
    cs = _child_start(refine, offsets)
    out = {"refine": refine, "owner": np.ones(n, bool),
           "level_offsets": offsets, "coords": coords}
    for name in FIELD_NAMES:
        v = np.zeros(n)
        v[leaves] = at_leaves[name]
        # intensive restriction: a coarse cell is the mean of its sons
        for lvl in range(len(offsets) - 3, -1, -1):
            a, b = int(offsets[lvl]), int(offsets[lvl + 1])
            idx = np.flatnonzero(refine[a:b]) + a
            if idx.size == 0:
                continue
            sons = v[(cs[idx][:, None] + np.arange(8)[None, :]).ravel()]
            v[idx] = sons.reshape(-1, 8).mean(axis=1)
        out[f"field:{name}"] = v
    return out


def variants(base: dict, k: int, amplitude: float, seed: int) -> list[dict]:
    """``k`` snapshots with ``base``'s structure and perturbed fields.

    Variant ``i`` multiplies every hydro field by ``exp(a * w_i(x))``,
    with ``w_i`` a sum of three seeded plane waves of unit amplitude at
    the node's centre, so the fields stay smooth and positive fields stay
    positive, and the magnetic field by one seeded factor ``exp(a * u_i)``,
    ``u_i`` uniform in [-1, 1], so that it stays divergence-free. The
    structure arrays are shared, not copied.
    """
    offsets = base["level_offsets"]
    n = base["refine"].shape[0]
    levels = _levels(offsets, n)
    centers = (base["coords"] + 0.5) / (1 << levels.astype(np.int64))[:, None]
    out = []
    for i in range(k):
        rng = np.random.default_rng([seed, i])
        wave = np.zeros(n)
        for _ in range(3):
            kvec = rng.integers(1, 4, size=3) * rng.choice([-1, 1], size=3)
            phase = rng.uniform(0, 2 * np.pi)
            wave += np.sin(2 * np.pi * (centers @ kvec) + phase)
        scale = np.exp(amplitude * wave / 3.0)
        b_scale = np.exp(amplitude * rng.uniform(-1.0, 1.0))
        snap = {key: v for key, v in base.items()
                if not key.startswith("field:")}
        for name in FIELD_NAMES:
            snap[f"field:{name}"] = base[f"field:{name}"] * (
                b_scale if name in B_NAMES else scale)
        out.append(snap)
    return out
