"""On-accelerator reduction: device staging + device reducers (§14).

The host engine's staging copies every snapshot to host memory *before*
any reduction — a full-resolution device→host transfer per staged step,
exactly the bottleneck the paper's in-transit architecture exists to
remove. This module keeps the snapshot on the accelerator end to end:

  * :class:`DeviceStagingArea` — the bounded-ring/backpressure staging
    area with **device-resident** buffer sets: a pushed jax array is
    restaged by a device→device copy (donation-safe, never touches the
    host), a host array is uploaded once; nothing crosses back to the
    host until a reducer has shrunk it.
  * a **device-reducer registry** (:func:`register_device_impl`) mapping
    the existing reducer classes to on-device implementations built on
    the Pallas rasterization kernels (``kernels/raster_kernel.py``,
    selected through ``kernels.ops``): axis-aligned slice, projection
    with owner masking, per-level histogram. Pixel geometry, plane hits
    and bin assignment are exact; values carry float32 rounding only
    (``tests/test_device_reduce.py``).
  * :class:`DeviceDAGRunner` — executes the engine's ReducerDAG with
    device implementations where registered and a **per-reducer host
    fallback** everywhere else (the full snapshot is materialized on
    host at most once per step, and only if some reducer needs it),
    while accounting every device→host byte (``stats``).

Wired in through ``InTransitEngine(device_reduce=True)``: the thread
backend stages into :class:`DeviceStagingArea` and lanes run the DAG
through the runner, so the only steady-state device→host traffic is the
reduced objects themselves (``bench_insitu.run_device`` records the
ratio). Device tables are 32-bit on every platform (:func:`device_dtype`:
float64 fields stage as float32, int64 as int32) because the TPU has no
float64; the float64 host reducers are the reference, and the outputs
meet the DESIGN.md §18 tolerances against them (slice 1e-6, projection
1e-4, histograms exact for the cast values). Kernel and ``ref`` backends
see the same float32 tables, so they agree bit for bit.

Device impl factories return ``None`` for configs the kernels do not
cover — non-power-of-two resolutions (the kernels' pixel geometry is
exact integer arithmetic). Reducers chained on an upstream ``source``
run on host but read only that upstream's already-transferred output,
so they never force a snapshot materialization; with the device-side
LOD cut the default CLI DAG has **zero** full-snapshot fallbacks.

``insitu.mesh_reduce`` builds the third path on these pieces: the same
DAG sharded over a JAX device mesh (``shard_map`` partial rasters +
on-device merge), selected with ``InTransitEngine(device_reduce="mesh")``.
"""
from __future__ import annotations

import threading

import numpy as np

from ..obs.trace import TRACER
from .reducers import (LevelHistogramReducer, LODCutReducer,
                       ProjectionReducer, ReducerDAG, SliceReducer)
from .staging import Snapshot, StagingArea

#: leaf-table padding bucket: bounds jit retraces as trees grow/shrink
#: (multiple of the raster kernels' lane block)
PAD_BUCKET = 4096


def _pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


def _padded(n: int) -> int:
    return -(-n // PAD_BUCKET) * PAD_BUCKET


def device_dtype(dtype) -> np.dtype:
    """The 32-bit dtype a host array takes on the device path."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f" and dtype.itemsize > 4:
        return np.dtype(np.float32)
    if dtype.kind in "iu" and dtype.itemsize > 4:
        return np.dtype(np.int32 if dtype.kind == "i" else np.uint32)
    return dtype


def to_device(x, *, copy: bool = False):
    """``x`` on the device as its :func:`device_dtype` (a new buffer
    even when it already is one, with ``copy``)."""
    import jax.numpy as jnp
    return jnp.array(x, dtype=device_dtype(x.dtype), copy=copy)


# --------------------------------------------------- parity contract

def cast_like_device(arrays: dict) -> dict:
    """Host arrays with every float rounded as the device tables round
    it (kept in the host dtype): the input of the "exact on the cast
    values" reference."""
    out = {}
    for k, v in arrays.items():
        v = np.asarray(v)
        out[k] = v.astype(device_dtype(v.dtype)).astype(v.dtype) \
            if v.dtype.kind == "f" else v
    return out


def parity_rtol(reducer) -> float | None:
    """DESIGN.md §18 limit for one reducer's device output.

    Projections (``sum`` merge) accumulate float32 adds: rtol 1e-4
    against the float64 host reducer. Histograms are exact against the
    host reducer over the cast values (``None``). Everything else only
    carries cast values (slices, LOD fields): rtol 1e-6.
    """
    return {"sum": 1e-4, "hist": None}.get(reducer.merge, 1e-6)


def parity_error(got: dict, want: dict) -> float:
    """Largest relative error of ``got`` against ``want``, key by key.

    Integer and boolean arrays must match exactly, dtype included, NaN
    positions must coincide; a mismatch of either is ``inf``.
    """
    if set(got) != set(want):
        return float("inf")
    err = 0.0
    for k, w in want.items():
        g, w = np.asarray(got[k]), np.asarray(w)
        if g.shape != w.shape:
            return float("inf")
        if w.dtype.kind != "f":
            if g.dtype != w.dtype or not np.array_equal(g, w):
                return float("inf")
            continue
        g, w = g.astype(np.float64), w.astype(np.float64)
        nan = np.isnan(w)
        if not np.array_equal(nan, np.isnan(g)):
            return float("inf")
        diff = np.abs(g[~nan] - w[~nan])
        scale = np.abs(w[~nan])
        if np.any(diff[scale == 0] > 0):
            return float("inf")
        rel = diff[scale > 0] / scale[scale > 0]
        err = max(err, float(rel.max()) if rel.size else 0.0)
    return err


def parity_check(reducer, got: dict, ref: dict, ref_cast: dict):
    """``(error, limit)`` of a device output under the §18 contract.

    ``ref`` is the host reducer's output on the float64 snapshot,
    ``ref_cast`` on :func:`cast_like_device` of it; the output passes
    when ``error <= limit``.
    """
    rtol = parity_rtol(reducer)
    if rtol is None:
        return parity_error(got, ref_cast), 0.0
    return parity_error(got, ref), rtol


# ------------------------------------------------------- device staging

class _DeviceBufferSet:
    """Device-resident twin of the host ``_BufferSet``.

    A jax-array push is staged through a **device→device copy** — it
    never crosses to the host, but it must not be a bare reference:
    the producer's buffer may be *donated* by its next jitted step
    (the trainer's train step donates the state), which deletes the
    original while the snapshot is still queued. Device restages count
    as buffer reuses (no host crossing), host uploads as allocs.
    ``block_until_ready`` keeps the ``push`` contract that compute may
    mutate (or donate) its arrays the moment push returns.
    """

    def __init__(self):
        self.buffers: dict = {}

    def fill(self, arrays: dict):
        import jax
        out = {}
        reuses = allocs = nbytes = 0
        for name, src in arrays.items():
            # a guaranteed copy: device sources may be donated away by
            # the producer's next step, host sources may alias on the
            # CPU backend
            out[name] = to_device(src, copy=True)
            if isinstance(src, jax.Array):
                reuses += 1          # device-resident: no host crossing
            else:
                allocs += 1          # host upload
            nbytes += out[name].nbytes
        jax.block_until_ready(out)
        # deliberately NOT retained on self: jax arrays cannot be
        # refilled in place, so holding them while the buffer set sits
        # in the free pool would only pin dead device memory — the
        # Snapshot owns the only reference, release() really frees
        return out, reuses, allocs, nbytes


class DeviceStagingArea(StagingArea):
    """StagingArea whose staged snapshots live on the accelerator.

    Same bounded queue, policies, stats and ``on_evict`` contract as the
    host area (it *is* the host area — only the buffer residency
    changes); ``Snapshot.arrays`` values are jax device arrays.
    """

    BUFFER_SET = _DeviceBufferSet


# ------------------------------------------------------------- prep

class DeviceTree:
    """Per-snapshot device view shared by all device reducer impls.

    Lazily derives the flat rasterization inputs from the staged BFS
    tree arrays — per-node levels (from ``level_offsets``, which never
    leaves the device), the owned-leaf validity mask, int32 coords —
    padded to :data:`PAD_BUCKET` so jit retraces stay bounded while the
    AMR tree changes size every step. Padding rows carry ``ok=False``.
    """

    def __init__(self, arrays: dict, n_domains: int, count_to_host=None,
                 backend: str | None = None):
        self.arrays = arrays
        self.n_domains = n_domains
        self.backend = backend
        self.count_to_host = count_to_host or (lambda nbytes: None)
        self.n_levels = int(arrays["level_offsets"].shape[0]) - 1
        self._geom = None
        self._fields: dict = {}

    def _pad(self, x, fill):
        import jax.numpy as jnp
        n = x.shape[0]
        pad = _padded(n) - n
        if pad == 0:
            return x
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, width, constant_values=fill)

    def _prep(self):
        if self._geom is None:
            import jax.numpy as jnp
            refine = to_device(self.arrays["refine"])
            n = int(refine.shape[0])
            offsets = to_device(self.arrays["level_offsets"])
            levels = (jnp.searchsorted(offsets, jnp.arange(n), side="right")
                      .astype(jnp.int32) - 1)
            ok = ~refine
            if self.n_domains > 1:   # partitioned: owned leaves count once
                ok = ok & to_device(self.arrays["owner"])
            coords = to_device(self.arrays["coords"]).astype(jnp.int32)
            self._geom = (self._pad(coords, 0), self._pad(levels, 0),
                          self._pad(ok, False))
        return self._geom

    @property
    def coords(self):
        return self._prep()[0]

    @property
    def levels(self):
        return self._prep()[1]

    @property
    def ok(self):
        """Valid-leaf mask: leaf ∧ (owner when partitioned) ∧ ¬padding."""
        return self._prep()[2]

    def field(self, name: str):
        if name not in self._fields:
            self._fields[name] = self._pad(
                to_device(self.arrays[f"field:{name}"]), 0)
        return self._fields[name]


# ----------------------------------------------------- impl registry

#: reducer class -> factory(reducer) -> impl(DeviceTree) -> dict | None
DEVICE_IMPLS: dict[type, object] = {}


def register_device_impl(reducer_cls: type):
    """Register (or replace) the device factory for one reducer class.

    The factory receives the reducer *instance* and returns either a
    callable ``impl(device_tree) -> dict of arrays`` or ``None`` when
    this configuration must fall back to the host implementation.
    """
    def deco(factory):
        DEVICE_IMPLS[reducer_cls] = factory
        return factory
    return deco


def device_impl_for(reducer):
    """Resolve one reducer instance to its device impl (or None)."""
    factory = DEVICE_IMPLS.get(type(reducer))
    return factory(reducer) if factory is not None else None


@register_device_impl(SliceReducer)
def _slice_impl(r: SliceReducer):
    if r.source is not None or not _pow2(r.resolution):
        return None

    def run(dt: DeviceTree):
        from ..kernels import ops
        img = ops.raster_slice(dt.coords, dt.levels, dt.field(r.field),
                               dt.ok, axis=r.axis, position=r.position,
                               resolution=r.resolution,
                               n_levels=dt.n_levels, backend=dt.backend)
        return {"image": img}
    return run


@register_device_impl(ProjectionReducer)
def _projection_impl(r: ProjectionReducer):
    if r.source is not None or not _pow2(r.resolution):
        return None

    def run(dt: DeviceTree):
        from ..kernels import ops
        img = ops.raster_projection(dt.coords, dt.levels, dt.field(r.field),
                                    dt.ok, axis=r.axis,
                                    resolution=r.resolution,
                                    n_levels=dt.n_levels,
                                    backend=dt.backend)
        return {"image": img}
    return run


@register_device_impl(LODCutReducer)
def _lod_impl(r: LODCutReducer):
    """Device-side LOD cut: slice the BFS prefix, demote the new floor.

    ``keep = levels <= max_level`` is a *prefix* of the level-major BFS
    arrays, so the host path's ``subset_tree`` selection is an identity
    re-index over the first ``offsets[max_level+1]`` rows: the cut is a
    device-side slice plus a ``refine=False`` stamp on the new deepest
    level (the host's ``force_leaf`` demotion). Only ``level_offsets``
    (a few dozen bytes, counted as meta) crosses to the host to size
    the slices; the cut tree itself crosses only as the reducer output.
    Kills the last full-snapshot fallback in the default CLI DAG.
    """
    def run(dt: DeviceTree):
        offs = np.asarray(dt.arrays["level_offsets"]).astype(np.int64)
        dt.count_to_host(offs.nbytes)
        if len(offs) - 1 <= r.max_level + 1:
            return dict(dt.arrays)          # already at/below the cut
        n_keep = int(offs[r.max_level + 1])
        new_offs = offs[:r.max_level + 2].copy()
        # trim now-empty deepest levels, exactly like subset_tree
        n_lv = len(new_offs) - 1
        while n_lv > 1 and new_offs[n_lv] == new_offs[n_lv - 1]:
            n_lv -= 1
        refine = to_device(dt.arrays["refine"])[:n_keep]
        refine = refine.at[int(offs[r.max_level]):n_keep].set(False)
        out = {"refine": refine, "level_offsets": new_offs[:n_lv + 1]}
        for k, v in dt.arrays.items():
            if k not in out and k != "level_offsets":
                out[k] = to_device(v)[:n_keep]
        return out
    return run


@register_device_impl(LevelHistogramReducer)
def _hist_impl(r: LevelHistogramReducer):
    def run(dt: DeviceTree):
        import jax.numpy as jnp

        from ..kernels import ops
        from ..kernels.raster_kernel import split_edges
        v = dt.field(r.field)
        if r.lo is None or r.hi is None:
            # auto bounds: one fused device min/max reduction, a single
            # 8-byte sync instead of the whole field (or two pulls)
            mm = np.asarray(jnp.stack(
                [jnp.min(jnp.where(dt.ok, v, jnp.inf)),
                 jnp.max(jnp.where(dt.ok, v, -jnp.inf))]))
            lo = float(mm[0]) if r.lo is None else r.lo
            hi = float(mm[1]) if r.hi is None else r.hi
            dt.count_to_host(mm.nbytes)
        else:
            lo, hi = r.lo, r.hi
        if hi <= lo:
            hi = lo + 1.0
        edges = np.linspace(lo, hi, r.bins + 1)
        hist = ops.raster_level_hist_partial(
            v, dt.levels, dt.ok, jnp.asarray(split_edges(edges)),
            n_levels=min(dt.n_levels, r.max_levels), backend=dt.backend)
        return {"hist": hist, "edges": edges}
    return run


# ------------------------------------------------------------ runner

class DeviceRunStats:
    """Device→host transfer accounting for the device-reduce path."""

    def __init__(self):
        self.snapshots = 0                 # snapshots run through the DAG
        self.device_objects = 0            # reduced objects computed on device
        self.bytes_reduced_to_host = 0     # transferred reduced outputs
        self.bytes_meta_to_host = 0        # scalar pulls (auto hist bounds)
        self.fallback_snapshots = 0        # snapshots materialized on host
        self.bytes_fallback_to_host = 0    # full-snapshot fallback transfers
        self.fallback_runs: dict[str, int] = {}   # per-reducer host runs

    def as_dict(self) -> dict:
        return {"snapshots": self.snapshots,
                "device_objects": self.device_objects,
                "bytes_reduced_to_host": self.bytes_reduced_to_host,
                "bytes_meta_to_host": self.bytes_meta_to_host,
                "fallback_snapshots": self.fallback_snapshots,
                "bytes_fallback_to_host": self.bytes_fallback_to_host,
                "fallback_runs": dict(self.fallback_runs),
                "bytes_to_host": (self.bytes_reduced_to_host
                                  + self.bytes_meta_to_host
                                  + self.bytes_fallback_to_host)}


class DeviceDAGRunner:
    """Execute a ReducerDAG with device impls + per-reducer host fallback.

    Drop-in for ``ReducerDAG.run`` on the engine's lane side: same kind
    filtering, dependency skipping and output shape. Reducers with a
    registered device impl reduce on the accelerator and transfer only
    their outputs; the rest see a host snapshot materialized at most
    once per step (and tensor reducers, which are jax-jitted anyway,
    consume the device arrays directly). Thread-safe — engine lanes may
    share one runner.
    """

    def __init__(self, dag: ReducerDAG, *, backend: str | None = None):
        self.dag = dag
        self.backend = backend          # kernel backend override (tests)
        self.impls = {r.name: device_impl_for(r) for r in dag}
        self.stats = DeviceRunStats()
        self._lock = threading.Lock()

    def device_reducers(self) -> list[str]:
        """Names of DAG reducers that will run on device."""
        return [n for n, impl in self.impls.items() if impl is not None]

    def _count_meta(self, nbytes: int) -> None:
        with self._lock:
            self.stats.bytes_meta_to_host += nbytes

    def _make_view(self, snap: Snapshot):
        """Per-snapshot view handed to the registered impls (overridable:
        the mesh runner builds sharded leaf tables here instead)."""
        return DeviceTree(snap.arrays, snap.n_domains, self._count_meta,
                          backend=self.backend)

    def run(self, snap: Snapshot) -> dict[str, dict[str, np.ndarray]]:
        import jax
        outputs: dict[str, dict[str, np.ndarray]] = {}
        dt = host_snap = None
        for r in self.dag.order:
            if snap.kind not in r.kinds:
                continue
            if any(d not in outputs for d in r.deps):
                continue
            impl = self.impls.get(r.name)
            if impl is not None:
                if dt is None:
                    dt = self._make_view(snap)
                moved = 0
                out = {}
                # spans nest under the lane's open "reduce" span: the
                # dispatch (the view's lazy prep and pads, the kernels'
                # launch), then the pull — np.asarray is where the async
                # device work and the device-to-host copy land
                with TRACER.span("device.transfer",
                                 args={"reducer": r.name}) as sp:
                    with TRACER.span("device.dispatch"):
                        res = impl(dt)
                    with TRACER.span("device.pull"):
                        for k, v in res.items():
                            if isinstance(v, jax.Array):
                                moved += v.nbytes
                                v = np.asarray(v)
                                if v.dtype.kind == "i":
                                    # the host reducers' integer width,
                                    # so catalogs hold one dtype on
                                    # every path
                                    v = v.astype(np.int64)
                            out[k] = v
                    sp.set(nbytes=moved)
                with self._lock:
                    self.stats.device_objects += 1
                    self.stats.bytes_reduced_to_host += moved
            elif getattr(r, "device_ready", False):
                # jax-jitted reducers (tensor norms/spectra) consume
                # device arrays directly; their outputs are already
                # reduced host arrays
                out = r.reduce(snap, outputs)
                with self._lock:
                    self.stats.device_objects += 1
                    self.stats.bytes_reduced_to_host += sum(
                        np.asarray(v).nbytes for v in out.values())
            elif getattr(r, "source", None):
                # source-chained reducers only read their upstream's
                # (already transferred) output — run them on host
                # without materializing the snapshot
                out = r.reduce(snap, outputs)
                with self._lock:
                    self.stats.fallback_runs[r.name] = \
                        self.stats.fallback_runs.get(r.name, 0) + 1
            else:
                if host_snap is None:
                    host_arrays, moved = {}, 0
                    with TRACER.span("device.transfer",
                                     args={"reducer": r.name,
                                           "fallback": True}) as sp, \
                            TRACER.span("device.pull"):
                        for k, v in snap.arrays.items():
                            if isinstance(v, jax.Array):
                                moved += v.nbytes
                            host_arrays[k] = np.asarray(v)
                        sp.set(nbytes=moved)
                    host_snap = Snapshot(
                        step=snap.step, kind=snap.kind,
                        arrays=host_arrays, meta=snap.meta,
                        domain=snap.domain, n_domains=snap.n_domains)
                    with self._lock:
                        self.stats.fallback_snapshots += 1
                        self.stats.bytes_fallback_to_host += moved
                out = r.reduce(host_snap, outputs)
                with self._lock:
                    self.stats.fallback_runs[r.name] = \
                        self.stats.fallback_runs.get(r.name, 0) + 1
            if out:
                outputs[r.name] = out
        with self._lock:
            self.stats.snapshots += 1
        return outputs
