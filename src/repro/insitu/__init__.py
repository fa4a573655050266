"""In-transit analysis: staged reductions from compute to lightweight HDep.

The paper's in-situ/in-transit layer (fig. 1): instead of dumping full
state for post-hoc processing, the compute flow stages snapshots to an
analysis flow that reduces them to purpose-specific lightweight objects
written at an independent cadence.

    compute --push--> StagingArea(group g) --pop--> worker lane g
                           (one per contributor group)   |
                                      reduced domain g of the shared
                                           per-step HDep context
                                                  |
                many viewers  <--LRU cache--   Catalog (merge-at-read)

  * :mod:`staging`   — double-buffered device→host hand-off with a bounded
    queue and explicit backpressure (``block``/``drop-oldest``/``subsample``).
  * :mod:`partition` — contributor-group split of a staged step (Hilbert
    leaf assignment for AMR trees, name striping for tensors).
  * :mod:`reducers`  — composable reduction operators over AMR trees and
    train states, combined in a DAG; each declares its multi-domain
    merge strategy.
  * :mod:`lanes`     — the lane runtime under both the engine and the
    async checkpoint manager: ``thread`` lanes (in-process workers) or
    ``process`` lanes (one OS process per contributor group over
    shared-memory staging).
  * :mod:`engine`    — per-group lanes consuming staged snapshots and
    writing reduced HDep domains at the engine's own output frequency.
  * :mod:`device`    — on-accelerator reduction: device-resident staging
    (``DeviceStagingArea``) plus a device-reducer registry over the
    Pallas rasterization kernels, so only *reduced* objects cross the
    device→host boundary (``InTransitEngine(device_reduce=True)``).
  * :mod:`mesh_reduce` — the sharded variant: each snapshot's leaf
    table is Hilbert-partitioned over a JAX device mesh, rasterized
    under ``shard_map`` and merged on device, so no device ever holds
    more than ~1/N of a snapshot (``device_reduce="mesh"``).
  * :mod:`catalog`   — the read side: cached, domain-merged queries for
    many concurrent viewers.
  * :mod:`serve`     — the continuous-batching serving core: in-flight
    identical queries coalesce onto one decode+merge (single-flight),
    region crops batch, admission control + per-client fairness bound
    overload, and ``fpdelta-pyramid`` levels stream coarse-first.
  * :mod:`server`    — the catalog as a service: many viewer *processes*
    share one reduction cache over HTTP (``RemoteCatalog`` client),
    routed through the serving engine.
"""
from .catalog import Catalog                                   # noqa: F401
from .engine import InTransitEngine                            # noqa: F401
from .partition import partition_snapshot                      # noqa: F401
from .reducers import (LevelHistogramReducer, LODCutReducer,   # noqa: F401
                       ProjectionReducer, Reducer, ReducerDAG,
                       SliceReducer, SpectraReducer, TensorNormReducer)
from .serve import (ProgressiveAssembler, ServeEngine,         # noqa: F401
                    ServeOverloaded, plan_progressive, staging_pressure)
from .server import CatalogBusy, CatalogServer, RemoteCatalog  # noqa: F401
from .staging import (POLICIES, ShmStagingArea, Snapshot,      # noqa: F401
                      StagingArea, StrideController)

_DEVICE_NAMES = ("DeviceStagingArea", "DeviceDAGRunner", "DeviceTree",
                 "register_device_impl", "device_impl_for")

_MESH_NAMES = ("MeshDAGRunner", "MeshRunStats", "MeshTable",
               "register_mesh_impl", "mesh_impl_for")


def __getattr__(name: str):
    # the device/mesh modules pull in jax at call time; keep the package
    # import light for the (host-only) CLI paths
    if name in _DEVICE_NAMES:
        from . import device
        return getattr(device, name)
    if name in _MESH_NAMES:
        from . import mesh_reduce
        return getattr(mesh_reduce, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
