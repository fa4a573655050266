"""Sharded multi-device reduction (insitu.mesh_reduce).

Parity contract: field tables are float32 (DESIGN.md §18), so the
shard_map path is bit-identical to the single-device runner over the
same tables wherever the arithmetic is order-free — slice painting (at
the collision-free resolution bound), integer level histograms — and
bit-identical to the ascending fold of per-domain device partials for
float projection sums. Against the float64 host reducers it meets the
§18 tolerances (slice 1e-6, projection 1e-4, hist exact on the cast
values); the LOD prefix cut runs on host and is exact.

Multi-device cases run in subprocesses: the forced host device count
(``XLA_FLAGS=--xla_force_host_platform_device_count``) must be set
before jax initializes a backend, and the parent test process already
initialized the default single-CPU one.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.insitu import Catalog, InTransitEngine
from repro.insitu.device import (DeviceDAGRunner, cast_like_device,
                                 parity_check)
from repro.insitu.mesh_reduce import MeshDAGRunner, mesh_impl_for
from repro.insitu.reducers import (LevelHistogramReducer, LODCutReducer,
                                   ProjectionReducer, ReducerDAG,
                                   SliceReducer)
from repro.insitu.staging import Snapshot
from repro.sim import amrgen, fields

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def tree():
    t = amrgen.generate_tree(fields.sedov(), min_level=2, max_level=5,
                             threshold=1.15, level_factor=1.05)
    t.validate()
    return t


def _dag(res=32, lod=3):
    return ReducerDAG([
        SliceReducer(field="density", axis=2, position=0.5, resolution=res),
        ProjectionReducer(field="density", axis=2, resolution=res),
        LevelHistogramReducer(field="density", bins=16),
        LODCutReducer(max_level=lod),
        SliceReducer(field="density", axis=2, position=0.5, resolution=res,
                     source=f"lod{lod}"),
    ])


def _host(dag, snap):
    out = {}
    for r in dag.order:
        o = r.reduce(snap, out)
        if o:
            out[r.name] = o
    return out


def _assert_same(got, want):
    """Bitwise equality, dtypes included."""
    assert sorted(got) == sorted(want)
    for name in want:
        for k, v in want[name].items():
            g = np.asarray(got[name][k])
            v = np.asarray(v)
            assert g.dtype == v.dtype, (name, k, g.dtype, v.dtype)
            np.testing.assert_array_equal(g, v, err_msg=f"{name}/{k}")


# ------------------------------------------------------ registry / config

def test_mesh_impl_registry_fallback_configs():
    assert mesh_impl_for(SliceReducer(resolution=64)) is not None
    assert mesh_impl_for(SliceReducer(resolution=100)) is None
    assert mesh_impl_for(SliceReducer(resolution=64, source="lod2")) is None
    assert mesh_impl_for(ProjectionReducer(resolution=48)) is None
    assert mesh_impl_for(LODCutReducer(max_level=2)) is not None
    assert mesh_impl_for(LevelHistogramReducer()) is not None


def test_engine_validates_mesh_config(tmp_path):
    mk = lambda: [SliceReducer(resolution=32)]  # noqa: E731
    with pytest.raises(ValueError, match="device_reduce mode"):
        InTransitEngine(str(tmp_path / "a"), mk(), device_reduce="tpu")
    with pytest.raises(ValueError, match="mesh_devices"):
        InTransitEngine(str(tmp_path / "b"), mk(), mesh_devices=2)
    with pytest.raises(ValueError, match="thread"):
        InTransitEngine(str(tmp_path / "c"), mk(), device_reduce="mesh",
                        backend="process")


def test_mesh_runner_rejects_oversized_mesh(tree):
    import jax
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        MeshDAGRunner(_dag(), devices=too_many)


# ------------------------------------------- single-device mesh (in-proc)

def test_mesh_single_device_bit_parity(tree):
    """S=1 degenerates to the single-device semantics: every raster
    (projection included — one shard, no fold) is bit-identical to the
    single-device runner, and the host-side LOD cut (plus the slice
    chained on it) to the host reducers."""
    dag = _dag()
    snap = Snapshot(step=0, kind="amr", arrays=tree.to_arrays())
    want = _host(dag, snap)
    single = DeviceDAGRunner(dag, backend="ref").run(snap)
    for name in ("slice-density-ax2-p0.5-r32", "proj-density-ax2-r32",
                 "hist-density-b16"):
        want[name] = single[name]
    runner = MeshDAGRunner(dag, devices=1, backend="ref")
    _assert_same(runner.run(snap), want)
    st = runner.stats.as_dict()
    assert st["fallback_snapshots"] == 0
    assert st["peak_leaf_frac"] == 1.0
    assert st["mesh_devices"] == 1
    assert st["bytes_tables_to_device"] > 0


def test_mesh_tiled_gather_bit_identical(tree):
    """A tile budget far below the table size streams the shard through
    carry-seeded kernels — outputs must not change by a single bit."""
    dag = _dag()
    snap = Snapshot(step=0, kind="amr", arrays=tree.to_arrays())
    whole = MeshDAGRunner(dag, devices=1, backend="ref").run(snap)
    for backend in ("ref", "pallas_interpret"):
        tiled = MeshDAGRunner(dag, devices=1, backend=backend,
                              tile_n=4096).run(snap)
        _assert_same(tiled, whole)


def test_mesh_f32_tolerance_parity(tree):
    """Field tables are float32: slice within 1e-6, projection within
    1e-4, histogram exact for the cast values."""
    dag = _dag()
    arrays = tree.to_arrays()
    snap = Snapshot(step=0, kind="amr", arrays=arrays)
    host = _host(dag, snap)
    out = MeshDAGRunner(dag, devices=1, backend="ref").run(snap)
    sname = "slice-density-ax2-p0.5-r32"
    pname = "proj-density-ax2-r32"
    hname = "hist-density-b16"
    assert np.asarray(out[sname]["image"]).dtype == np.float32
    np.testing.assert_allclose(
        np.asarray(out[sname]["image"], np.float64), host[sname]["image"],
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out[pname]["image"], np.float64), host[pname]["image"],
        rtol=1e-4)
    # exact-on-cast-values: the host reducer over the f32-rounded field
    # must reproduce the f32 histogram bin by bin (edges included:
    # auto bounds come from the cast values, f32->f64 promotion exact)
    cast = dict(arrays)
    cast["field:density"] = (arrays["field:density"]
                             .astype(np.float32).astype(np.float64))
    cast_host = _host(dag, Snapshot(step=0, kind="amr", arrays=cast))
    np.testing.assert_array_equal(np.asarray(out[hname]["hist"]),
                                  cast_host[hname]["hist"])
    np.testing.assert_array_equal(np.asarray(out[hname]["edges"]),
                                  cast_host[hname]["edges"])


def test_mesh_lod_cut_and_chained_slice(tree):
    """The mesh LOD impl (host prefix slice) equals the host subset_tree
    cut, and the chained slice consumes it without any snapshot
    fallback."""
    dag = _dag()
    snap = Snapshot(step=0, kind="amr", arrays=tree.to_arrays())
    host = _host(dag, snap)
    runner = MeshDAGRunner(dag, devices=1, backend="ref")
    out = runner.run(snap)
    for k, v in host["lod3"].items():
        np.testing.assert_array_equal(np.asarray(out["lod3"][k]), v,
                                      err_msg=k)
    assert runner.stats.fallback_snapshots == 0
    # the chained slice is the only host-run reducer, fed from upstream
    assert set(runner.stats.fallback_runs) == {
        "slice-density-ax2-p0.5-r32-of-lod3"}


def test_mesh_nonpow2_resolution_falls_back(tree):
    dag = ReducerDAG([SliceReducer(field="density", resolution=48)])
    snap = Snapshot(step=0, kind="amr", arrays=tree.to_arrays())
    runner = MeshDAGRunner(dag, devices=1, backend="ref")
    assert runner.impls[dag.order[0].name] is None
    out = runner.run(snap)
    np.testing.assert_array_equal(out[dag.order[0].name]["image"],
                                  dag.order[0].reduce(snap, {})["image"])
    # host arrays never left the host: the fallback moved zero bytes
    assert runner.stats.bytes_fallback_to_host == 0
    assert runner.stats.fallback_snapshots == 1


def test_engine_mesh_end_to_end_catalog(tree, tmp_path):
    """device_reduce='mesh' writes a catalog matching the host engine
    under the §18 contract."""
    roots = {}
    for mode, kw in (("host", {}),
                     ("mesh", dict(device_reduce="mesh"))):
        roots[mode] = str(tmp_path / mode)
        eng = InTransitEngine(roots[mode], list(_dag()), policy="block",
                              **kw).start()
        assert eng.submit(0, tree)
        eng.close()
        if mode == "mesh":
            ds = eng.device_stats
            assert ds["mesh_devices"] == 1
            assert ds["fallback_snapshots"] == 0
        else:
            assert eng.device_stats is None
    ch, cm = Catalog(roots["host"]), Catalog(roots["mesh"])
    assert ch.reducers(0) == cm.reducers(0)
    dag = _dag()
    cast = _host(dag, Snapshot(step=0, kind="amr",
                               arrays=cast_like_device(tree.to_arrays())))
    for r in dag.order:
        err, limit = parity_check(r, cm.query(0, r.name),
                                  ch.query(0, r.name), cast[r.name])
        assert err <= limit, (r.name, err, limit)
    ch.close()
    cm.close()


# --------------------------------------------- multi-device (subprocess)

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import jax
assert len(jax.devices()) == 4

from repro.insitu.device import (DeviceDAGRunner, cast_like_device,
                                 parity_check)
from repro.insitu.mesh_reduce import MeshDAGRunner
from repro.insitu.partition import leaf_shards, partition_snapshot
from repro.insitu.reducers import (LevelHistogramReducer, LODCutReducer,
                                   ProjectionReducer, ReducerDAG,
                                   SliceReducer)
from repro.insitu.staging import Snapshot
from repro.sim import amrgen, fields

tree = amrgen.generate_tree(fields.sedov(), min_level=2, max_level=5,
                            threshold=1.15, level_factor=1.05)
arrays = tree.to_arrays()
R = 32
dag = ReducerDAG([
    SliceReducer(field="density", axis=2, position=0.5, resolution=R),
    ProjectionReducer(field="density", axis=2, resolution=R),
    LevelHistogramReducer(field="density", bins=16),
    LODCutReducer(max_level=3),
    SliceReducer(field="density", axis=2, position=0.5, resolution=R,
                 source="lod3"),
])
pname = "proj-density-ax2-r%d" % R
snap = Snapshot(step=0, kind="amr", arrays=arrays)
host = dag.run(snap)
host_cast = dag.run(Snapshot(step=0, kind="amr",
                             arrays=cast_like_device(arrays)))

refine = np.asarray(arrays["refine"])
leaves = np.flatnonzero(~refine)
proj_dag = ReducerDAG([next(r for r in dag.order if r.name == pname)])

def md_fold(S):
    # read-side reference: per-Hilbert-domain device reduce over the
    # same float32 tables, folded in ascending domain order
    shard = leaf_shards(arrays, S)
    acc = None
    for g in range(S):
        arr2 = dict(arrays)
        owner = np.zeros(refine.shape[0], bool)
        owner[leaves[shard == g]] = True
        arr2["owner"] = owner
        part = DeviceDAGRunner(proj_dag, backend="ref").run(
            Snapshot(step=0, kind="amr", arrays=arr2,
                     n_domains=2))[pname]["image"]
        acc = part if acc is None else acc + part
    return acc

for S in (1, 2, 4, 3):          # 3: the all_gather+argmax merge branch
    runner = MeshDAGRunner(dag, devices=S, backend="ref")
    out = runner.run(snap)
    for r in dag.order:
        err, limit = parity_check(r, out[r.name], host[r.name],
                                  host_cast[r.name])
        assert err <= limit, (S, r.name, err, limit)
    assert np.array_equal(np.asarray(out[pname]["image"]), md_fold(S)), S
    st = runner.stats.as_dict()
    assert st["fallback_snapshots"] == 0
    assert st["mesh_devices"] == S
    # residency proof: no device holds more than ~1/S of the leaf rows
    if S == 4:
        assert st["peak_leaf_frac"] <= 0.6, st["peak_leaf_frac"]
        assert st["peak_device_table_bytes"] * S <= \
            st["bytes_tables_to_device"] * 1.01
        assert st["peak_device_partial_bytes"] > 0
    print("PARITY-OK", S, round(st["peak_leaf_frac"], 4))

# tiled-gather under shard_map: bit-identical to the untiled mesh
whole = MeshDAGRunner(dag, devices=4, backend="ref").run(snap)
tiled = MeshDAGRunner(dag, devices=4, backend="ref", tile_n=4096).run(snap)
for name, o in whole.items():
    for k, v in o.items():
        assert np.array_equal(np.asarray(tiled[name][k]), np.asarray(v),
                              equal_nan=True), ("tiled", name, k)
print("TILED-OK")

# owner-masked contributor partitions compose with the mesh
parts = partition_snapshot(arrays, "amr", 2)
runner = MeshDAGRunner(dag, devices=4, backend="ref")
slice_img = None
proj_img = None
hist = None
sname = "slice-density-ax2-p0.5-r%d" % R
for d, pa in enumerate(parts):
    out = runner.run(Snapshot(step=0, kind="amr", arrays=pa, domain=d,
                              n_domains=2))
    s = np.asarray(out[sname]["image"])
    slice_img = s if slice_img is None else np.where(
        np.isnan(slice_img), s, slice_img)
    p = np.asarray(out[pname]["image"])
    proj_img = p if proj_img is None else proj_img + p
    h = np.asarray(out["hist-density-b16"]["hist"])
    hist = h if hist is None else None  # per-part auto edges differ; skip sum
assert np.array_equal(slice_img, host_cast[sname]["image"], equal_nan=True)
np.testing.assert_allclose(proj_img, host[pname]["image"], rtol=1e-4)
print("PARTITION-OK")
"""


def test_mesh_forced_host_devices_subprocess(tmp_path):
    """1/2/4-device parity, the non-pow2 merge branch, tiling and
    owner-masked partitions — under 4 forced host devices."""
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         env={**os.environ, "PYTHONPATH": SRC},
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    for marker in ("PARITY-OK 1", "PARITY-OK 2", "PARITY-OK 4",
                   "PARITY-OK 3", "TILED-OK", "PARTITION-OK"):
        assert marker in out.stdout, (marker, out.stdout)
