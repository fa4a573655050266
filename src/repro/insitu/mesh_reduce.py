"""Sharded multi-device reduction: shard_map'd rasters + on-device merge.

The PR 5 device path (``insitu.device``) funnels the whole reduction
DAG through one device — the paper's single-funnel bottleneck one layer
down. This module partitions each snapshot's *leaf table* over a JAX
device mesh with the same Hilbert split the multi-domain writer uses
(``partition.leaf_shards``), runs the Pallas raster kernels under
``shard_map`` so every device rasterizes only its own leaf shard into a
partial image, and merges the partials **on device** with the exact
semantics of the read-side merge strategies (``hercule.api``):

  ===========  ======================  ==================================
  reducer      read-side strategy      on-device merge
  ===========  ======================  ==================================
  slice        ``tile`` (paint)        depth-resolve: deepest leaf wins,
                                       lowest shard on ties — a ppermute
                                       XOR-butterfly tree over pow2
                                       meshes, all_gather + argmax else
  projection   ``sum`` (ascending)     all_gather + static ascending
                                       fold — the same float adds in the
                                       same order as ``_merge_sum``
  level-hist   ``hist`` (int sum)      ``psum`` (integer counts are
                                       order-free, so the psum tree is
                                       exact)
  ===========  ======================  ==================================

No full snapshot or full leaf table ever materializes on one device:
each device holds its own ~1/S of the leaf rows (padded to the common
bucket) plus one partial image; :class:`MeshRunStats` accounts for both
(``peak_leaf_frac``, ``peak_device_table_bytes``,
``peak_device_partial_bytes``) next to the inherited device→host byte
counters.

Bit-parity contract (``tests/test_mesh_reduce.py``): per-shard rows are
the global BFS-ordered leaves of one Hilbert segment — exactly the
leaves the multi-domain writer assigns to domain ``g`` — so shard
partials are bitwise the per-domain device outputs over the same
float32 tables, and the merged images are bit-identical to the
read-side merge of those (slice requires ``resolution >= 2**max_level``,
where leaf footprints are disjoint and painting is collision-free; the
read-side tile merge has the same contract). Field tables are float32
on every platform (``device.device_dtype``), so against the float64
host reducers the contract is tolerance parity (DESIGN.md §18: slice
rtol 1e-6, projection rtol 1e-4, histograms exact *for the cast
values*).

Leaf tables larger than the per-shard padded budget (``tile_n``) switch
to the tiled-gather formulation (``ops`` ``tile_n=``): the shard's rows
stream through carry-seeded kernels in BFS-order chunks, bounding the
gathered working set without changing a single output bit.

Develop/CI-test on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag must
be set before jax initializes a backend — the tests and the bench spawn
subprocess children). Select with ``InTransitEngine(device_reduce="mesh")``
/ ``launch/insitu.py --device-mesh N`` / the trainer's
``insitu_device_mesh``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..kernels.raster_kernel import split_edges
from .device import (DeviceDAGRunner, DeviceRunStats, _padded, _pow2,
                     device_dtype)
from .partition import leaf_shards
from .reducers import (LevelHistogramReducer, LODCutReducer,
                       ProjectionReducer, ReducerDAG, SliceReducer)
from .staging import Snapshot

__all__ = ["MeshDAGRunner", "MeshRunStats", "MeshTable",
           "register_mesh_impl", "mesh_impl_for", "MESH_AXIS", "MESH_TILE"]

#: mesh axis name the shard_map bodies reduce over
MESH_AXIS = "shard"

#: per-shard padded row budget before the tiled-gather formulation kicks
#: in (multiple of the kernels' lane block)
MESH_TILE = 16384


# ----------------------------------------------------------- leaf tables

class MeshTable:
    """Per-snapshot sharded leaf table (the mesh twin of ``DeviceTree``).

    Built host-side from the staged (host-resident) BFS tree arrays:
    owned leaves are split into Hilbert-contiguous shards
    (:func:`partition.leaf_shards`), each shard's rows keep ascending
    BFS order, every shard is padded to the common bucket multiple, and
    the stacked ``(S, P, ...)`` arrays are uploaded once under a
    ``NamedSharding`` so device ``g`` receives only shard ``g``'s rows.
    Fields upload lazily per reducer, cast at table build to their
    device dtype (float32).
    """

    def __init__(self, arrays: dict, n_domains: int, mesh: Mesh, *,
                 backend: str | None = None, tile_n: int = MESH_TILE,
                 on_upload=None):
        self.arrays = arrays
        self.mesh = mesh
        self.backend = backend
        self.tile_n = tile_n
        self.on_upload = on_upload or (lambda nbytes: None)
        self.n_shards = int(mesh.devices.size)
        self._offsets = np.asarray(arrays["level_offsets"])
        self.n_levels = int(self._offsets.shape[0]) - 1
        leaves = np.flatnonzero(~np.asarray(arrays["refine"]))
        shard = leaf_shards(arrays, self.n_shards)
        if n_domains > 1:            # partitioned: owned leaves count once
            owned = np.asarray(arrays["owner"])[leaves]
            leaves, shard = leaves[owned], shard[owned]
        self._rows = [leaves[shard == g] for g in range(self.n_shards)]
        counts = [int(r.shape[0]) for r in self._rows]
        self.total_rows = int(leaves.shape[0])
        self.peak_rows = max(counts) if counts else 0
        self.rows_padded = _padded(max(self.peak_rows, 1))
        self._geom = None
        self._fields: dict = {}

    @property
    def leaf_frac(self) -> float:
        """Largest per-device share of the (unpadded) leaf rows."""
        return self.peak_rows / max(self.total_rows, 1)

    def _stack(self, per_row, dtype, fill, trailing=()):
        out = np.full((self.n_shards, self.rows_padded, *trailing), fill,
                      dtype)
        for g, rows in enumerate(self._rows):
            out[g, :rows.shape[0]] = per_row(rows)
        return out

    def _shard(self, host: np.ndarray):
        spec = PartitionSpec(MESH_AXIS, *([None] * (host.ndim - 1)))
        arr = jax.device_put(host, NamedSharding(self.mesh, spec))
        arr.block_until_ready()
        self.on_upload(arr.nbytes)
        return arr

    def _prep(self):
        if self._geom is None:
            coords = np.asarray(self.arrays["coords"]).astype(np.int32)
            self._geom = (
                self._shard(self._stack(lambda rows: coords[rows],
                                        np.int32, 0, trailing=(3,))),
                self._shard(self._stack(
                    lambda rows: np.searchsorted(
                        self._offsets, rows, side="right").astype(np.int32)
                    - 1, np.int32, 0)),
                self._shard(self._stack(lambda rows: True, bool, False)))
        return self._geom

    @property
    def coords(self):
        return self._prep()[0]

    @property
    def levels(self):
        return self._prep()[1]

    @property
    def ok(self):
        """Valid-row mask: padding rows carry ``ok=False``."""
        return self._prep()[2]

    def _host_field(self, name: str) -> np.ndarray:
        v = np.asarray(self.arrays[f"field:{name}"])
        return v.astype(device_dtype(v.dtype))

    def field(self, name: str):
        if name not in self._fields:
            v = self._host_field(name)
            self._fields[name] = self._shard(
                self._stack(lambda rows: v[rows], v.dtype, 0))
        return self._fields[name]

    def field_bounds(self, name: str) -> tuple[float, float]:
        """Host-side min/max over the owned (cast) leaf values.

        min/max are order-free, so this is bitwise the host reducer's
        auto bounds over the cast values — and it costs no device pull
        at all (the staged arrays are host-resident on the mesh path,
        vs. the single-device path's fused-reduction 8-byte sync).
        """
        v = self._host_field(name)
        vals = [v[rows] for rows in self._rows if rows.size]
        if not vals:
            return 0.0, 1.0
        allv = np.concatenate(vals)
        return float(allv.min()), float(allv.max())


# ------------------------------------------------------ on-device merges

def _depth_resolve(img, depth, n_shards: int):
    """Slice merge: deepest leaf wins; equal depth → lowest shard.

    For power-of-two meshes this is a ppermute XOR-butterfly — after
    ``log2(S)`` exchange stages every device holds the global winner,
    because the (depth, -shard) lexicographic max is associative and
    commutative. Other mesh sizes take one all_gather + ``argmax``
    (which returns the *first* maximum, i.e. the lowest shard). Ties can
    only occur below the collision-free resolution bound; at or above it
    the two forms are identical pixel for pixel.
    """
    if n_shards == 1:
        return img, depth
    if _pow2(n_shards):
        src = jnp.full(img.shape, jax.lax.axis_index(MESH_AXIS), jnp.int32)
        m = 1
        while m < n_shards:
            perm = [(i, i ^ m) for i in range(n_shards)]
            img_p = jax.lax.ppermute(img, MESH_AXIS, perm)
            depth_p = jax.lax.ppermute(depth, MESH_AXIS, perm)
            src_p = jax.lax.ppermute(src, MESH_AXIS, perm)
            take = (depth_p > depth) | ((depth_p == depth) & (src_p < src))
            img = jnp.where(take, img_p, img)
            depth = jnp.where(take, depth_p, depth)
            src = jnp.where(take, src_p, src)
            m <<= 1
        return img, depth
    d_all = jax.lax.all_gather(depth, MESH_AXIS)        # (S, R, R)
    i_all = jax.lax.all_gather(img, MESH_AXIS)
    win = jnp.argmax(d_all, axis=0)[None]
    return (jnp.take_along_axis(i_all, win, 0)[0],
            jnp.take_along_axis(d_all, win, 0)[0])


def _ordered_sum(img, n_shards: int):
    """Projection merge: the read-side ``_merge_sum`` ascending fold.

    A float ``psum`` sums in whatever order the lowering picks, not the
    merge registry's — so gather the S partials and fold them in static
    ascending shard order instead: every float add happens in the same
    sequence as the host merge (bit-identical), and the gather, not the
    unrolled fold, is the O(S·R²) cost.
    """
    if n_shards == 1:
        return img
    parts = jax.lax.all_gather(img, MESH_AXIS)          # (S, R, R)
    acc = parts[0]
    for i in range(1, n_shards):
        acc = acc + parts[i]
    return acc


# ------------------------------------------------- shard_map'd reductions

_TBL = (PartitionSpec(MESH_AXIS),) * 4


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "position", "resolution", "n_levels", "backend",
    "tile_n"))
def _mesh_slice(coords, levels, ok, values, *, mesh: Mesh, axis: int,
                position: float, resolution: int, n_levels: int,
                backend: str | None, tile_n: int):
    from ..kernels import ops

    def body(c, lv, okk, val):
        img, depth = ops.raster_slice_partial(
            c[0], lv[0], val[0], okk[0], axis=axis, position=position,
            resolution=resolution, n_levels=n_levels, backend=backend,
            tile_n=tile_n)
        img, _ = _depth_resolve(img, depth, int(mesh.devices.size))
        return img

    # check_vma=False: the butterfly's ppermute is not *provably*
    # replicated to the checker, though every device holds the same
    # winner after the last stage
    f = jax.shard_map(body, mesh=mesh, in_specs=_TBL,
                      out_specs=PartitionSpec(), check_vma=False)
    return f(coords, levels, ok, values)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "resolution", "n_levels", "backend", "tile_n"))
def _mesh_projection(coords, levels, ok, values, *, mesh: Mesh, axis: int,
                     resolution: int, n_levels: int, backend: str | None,
                     tile_n: int):
    from ..kernels import ops

    def body(c, lv, okk, val):
        img = ops.raster_projection_partial(
            c[0], lv[0], val[0], okk[0], axis=axis, resolution=resolution,
            n_levels=n_levels, backend=backend, tile_n=tile_n)
        return _ordered_sum(img, int(mesh.devices.size))

    f = jax.shard_map(body, mesh=mesh, in_specs=_TBL,
                      out_specs=PartitionSpec(), check_vma=False)
    return f(coords, levels, ok, values)


@functools.partial(jax.jit, static_argnames=("mesh", "n_levels", "backend"))
def _mesh_hist(values, levels, ok, edges2, *, mesh: Mesh, n_levels: int,
               backend: str | None):
    from ..kernels import ops

    def body(val, lv, okk, e):
        hist = ops.raster_level_hist_partial(
            val[0], lv[0], okk[0], e, n_levels=n_levels, backend=backend)
        return jax.lax.psum(hist, MESH_AXIS)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(*_TBL[:3], PartitionSpec()),
                      out_specs=PartitionSpec(), check_vma=False)
    return f(values, levels, ok, edges2)


# ----------------------------------------------------- impl registry

#: reducer class -> factory(reducer) -> impl(MeshTable) -> dict | None
MESH_IMPLS: dict[type, object] = {}


def register_mesh_impl(reducer_cls: type):
    """Register (or replace) the mesh factory for one reducer class.

    Mirrors :func:`device.register_device_impl`: the factory receives
    the reducer *instance* and returns ``impl(mesh_table) -> dict`` or
    ``None`` when this configuration must fall back to the host path.
    """
    def deco(factory):
        MESH_IMPLS[reducer_cls] = factory
        return factory
    return deco


def mesh_impl_for(reducer):
    """Resolve one reducer instance to its mesh impl (or None)."""
    factory = MESH_IMPLS.get(type(reducer))
    return factory(reducer) if factory is not None else None


@register_mesh_impl(SliceReducer)
def _slice_mesh(r: SliceReducer):
    if r.source is not None or not _pow2(r.resolution):
        return None

    def run(mt: MeshTable):
        img = _mesh_slice(mt.coords, mt.levels, mt.ok, mt.field(r.field),
                          mesh=mt.mesh, axis=r.axis, position=r.position,
                          resolution=r.resolution, n_levels=mt.n_levels,
                          backend=mt.backend, tile_n=mt.tile_n)
        return {"image": img}
    return run


@register_mesh_impl(ProjectionReducer)
def _projection_mesh(r: ProjectionReducer):
    if r.source is not None or not _pow2(r.resolution):
        return None

    def run(mt: MeshTable):
        img = _mesh_projection(mt.coords, mt.levels, mt.ok,
                               mt.field(r.field), mesh=mt.mesh, axis=r.axis,
                               resolution=r.resolution,
                               n_levels=mt.n_levels, backend=mt.backend,
                               tile_n=mt.tile_n)
        return {"image": img}
    return run


@register_mesh_impl(LODCutReducer)
def _lod_mesh(r: LODCutReducer):
    """LOD cut on the mesh path: a pure-numpy BFS prefix slice.

    Mesh snapshots stage on host, so the cut never needs a device at
    all — it is the same prefix-slice + deepest-level demotion identity
    the device impl uses (``device._lod_impl``), on the host arrays.
    Registered so the default CLI DAG reports zero fallbacks on the
    mesh path too.
    """
    def run(mt: MeshTable):
        offs = np.asarray(mt.arrays["level_offsets"]).astype(np.int64)
        if len(offs) - 1 <= r.max_level + 1:
            return {k: np.asarray(v) for k, v in mt.arrays.items()}
        n_keep = int(offs[r.max_level + 1])
        new_offs = offs[:r.max_level + 2].copy()
        # trim now-empty deepest levels, exactly like subset_tree
        n_lv = len(new_offs) - 1
        while n_lv > 1 and new_offs[n_lv] == new_offs[n_lv - 1]:
            n_lv -= 1
        refine = np.array(np.asarray(mt.arrays["refine"])[:n_keep])
        refine[int(offs[r.max_level]):n_keep] = False
        out = {"refine": refine, "level_offsets": new_offs[:n_lv + 1]}
        for k, v in mt.arrays.items():
            if k not in out and k != "level_offsets":
                out[k] = np.asarray(v)[:n_keep]
        return out
    return run


@register_mesh_impl(LevelHistogramReducer)
def _hist_mesh(r: LevelHistogramReducer):
    def run(mt: MeshTable):
        if r.lo is None or r.hi is None:
            lo, hi = mt.field_bounds(r.field)
            lo = lo if r.lo is None else r.lo
            hi = hi if r.hi is None else r.hi
        else:
            lo, hi = r.lo, r.hi
        if hi <= lo:
            hi = lo + 1.0
        edges = np.linspace(lo, hi, r.bins + 1)
        hist = _mesh_hist(mt.field(r.field), mt.levels, mt.ok,
                          jnp.asarray(split_edges(edges)), mesh=mt.mesh,
                          n_levels=min(mt.n_levels, r.max_levels),
                          backend=mt.backend)
        return {"hist": hist, "edges": edges}
    return run


# ------------------------------------------------------------ runner

class MeshRunStats(DeviceRunStats):
    """Transfer + residency accounting for the mesh path.

    Extends the device counters with the proof obligations of the
    sharded layout: the largest per-device share of the leaf rows
    (``peak_leaf_frac``, ≈ 1/S for a balanced Hilbert split), the
    per-device table upload and the per-device partial-image footprint.
    """

    def __init__(self):
        super().__init__()
        self.mesh_devices = 0
        self.leaf_rows = 0                    # cumulative sharded rows
        self.peak_leaf_frac = 0.0             # max per-device row share
        self.bytes_tables_to_device = 0       # total sharded uploads
        self.peak_device_table_bytes = 0      # one shard's padded rows
        self.peak_device_partial_bytes = 0    # one partial image / hist

    def as_dict(self) -> dict:
        d = super().as_dict()
        d.update(mesh_devices=self.mesh_devices,
                 leaf_rows=self.leaf_rows,
                 peak_leaf_frac=self.peak_leaf_frac,
                 bytes_tables_to_device=self.bytes_tables_to_device,
                 peak_device_table_bytes=self.peak_device_table_bytes,
                 peak_device_partial_bytes=self.peak_device_partial_bytes)
        return d


class MeshDAGRunner(DeviceDAGRunner):
    """DeviceDAGRunner whose impls shard every snapshot over a mesh.

    Drop-in third path for the engine (``device_reduce="mesh"``): same
    DAG order, per-reducer fallback and output contract as the
    single-device runner — but snapshots stage on *host*, the leaf
    table is Hilbert-sharded over the first ``devices`` jax devices and
    reduced under ``shard_map``, and host fallbacks cost no device
    traffic (the staged arrays never left the host).
    """

    def __init__(self, dag: ReducerDAG, *, devices: int | None = None,
                 backend: str | None = None, tile_n: int = MESH_TILE):
        avail = jax.devices()
        n = len(avail) if devices in (None, 0) else int(devices)
        if not 1 <= n <= len(avail):
            raise ValueError(
                f"device mesh of {n} requested but only {len(avail)} jax "
                f"device(s) available (forcing host devices needs "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N set "
                f"before jax initializes)")
        self.mesh = Mesh(np.asarray(avail[:n]), (MESH_AXIS,))
        self.tile_n = tile_n
        super().__init__(dag, backend=backend)
        self.impls = {r.name: mesh_impl_for(r) for r in dag}
        self.stats = MeshRunStats()
        self.stats.mesh_devices = n

    def _note_upload(self, nbytes: int) -> None:
        with self._lock:
            self.stats.bytes_tables_to_device += nbytes
            per_dev = nbytes // max(self.stats.mesh_devices, 1)
            self.stats.peak_device_table_bytes = max(
                self.stats.peak_device_table_bytes, per_dev)

    def _make_view(self, snap: Snapshot):
        mt = MeshTable(snap.arrays, snap.n_domains, self.mesh,
                       backend=self.backend, tile_n=self.tile_n,
                       on_upload=self._note_upload)
        with self._lock:
            self.stats.leaf_rows += mt.total_rows
            self.stats.peak_leaf_frac = max(self.stats.peak_leaf_frac,
                                            mt.leaf_frac)
        return mt

    def run(self, snap: Snapshot):
        outputs = super().run(snap)
        # every device holds one replicated copy of each reduced object
        # while its merge runs; the largest single output bounds the
        # per-device partial footprint
        peak = 0
        for out in outputs.values():
            peak = max(peak, sum(np.asarray(v).nbytes
                                 for v in out.values()))
        with self._lock:
            self.stats.peak_device_partial_bytes = max(
                self.stats.peak_device_partial_bytes, peak)
        return outputs
