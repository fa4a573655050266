"""On-accelerator reduction subsystem (DESIGN.md §14).

Kernel parity is the acceptance criterion: for slice / projection /
per-level histogram, ``pallas_interpret`` == ``ref`` bit for bit (both
see the same float32 device tables), and both meet the DESIGN.md §18
tolerances against the float64 host-numpy reducers on random AMR trees
— including owner-masked partitioned inputs. Plus device staging
semantics (device-resident 32-bit snapshots, push-copy safety,
backpressure parity) and the end-to-end
``InTransitEngine(device_reduce=True)`` path (§18-equal catalogs, host
fallback for unregistered reducers, transfer accounting).
"""
import numpy as np
import pytest

from repro.insitu import Catalog, InTransitEngine, partition_snapshot
from repro.insitu.device import (DeviceDAGRunner, DeviceStagingArea,
                                 cast_like_device, device_impl_for,
                                 parity_check)
from repro.insitu.reducers import (LevelHistogramReducer, LODCutReducer,
                                   ProjectionReducer, ReducerDAG,
                                   SliceReducer)
from repro.insitu.staging import Snapshot
from repro.sim import amrgen, fields

SEEDS = (0, 7)
RESOLUTIONS = (16, 64)    # 16 < deepest level: exercises px==1 collisions


def random_tree(seed: int):
    """A Sedov AMR structure carrying random (sign-mixed) field values."""
    rng = np.random.default_rng(seed)
    tree = amrgen.generate_tree(fields.sedov(r_shock=0.2 + 0.1 * rng.random()),
                                min_level=2, max_level=5, threshold=1.2)
    tree.fields["density"] = rng.standard_normal(tree.n_nodes) * 4.0 + 1.0
    return tree


def host_outputs(snap, resolution):
    dag = ReducerDAG([
        SliceReducer(field="density", axis=2, position=0.5,
                     resolution=resolution),
        ProjectionReducer(field="density", axis=2, resolution=resolution),
        LevelHistogramReducer(field="density", bins=32),
    ])
    return dag, dag.run(snap)


def assert_tree_equal(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def assert_parity(dag, got: dict, arrays: dict, n_domains: int = 1,
                  domain: int = 0):
    """Every device output within its §18 limit of the host reducers."""
    def host(a):
        return dag.run(Snapshot(step=0, kind="amr", arrays=a, domain=domain,
                                n_domains=n_domains))
    ref, ref_cast = host(arrays), host(cast_like_device(arrays))
    assert set(got) == set(ref)
    for r in dag.order:
        err, limit = parity_check(r, got[r.name], ref[r.name],
                                  ref_cast[r.name])
        assert err <= limit, (r.name, err, limit)


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("resolution", RESOLUTIONS)
def test_kernel_parity_single_domain(seed, resolution):
    """pallas_interpret == ref bit for bit; both within §18 of host."""
    tree = random_tree(seed)
    arrays = tree.to_arrays()
    snap = Snapshot(step=0, kind="amr", arrays=arrays)
    dag, host = host_outputs(snap, resolution)
    dev = {}
    for backend in ("ref", "pallas_interpret"):
        runner = DeviceDAGRunner(dag, backend=backend)
        dev[backend] = runner.run(snap)
        assert not runner.stats.fallback_runs
    for rname in host:
        assert_tree_equal(dev["ref"][rname], dev["pallas_interpret"][rname])
    assert_parity(dag, dev["pallas_interpret"], arrays)


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_kernel_parity_owner_masked_partitions(backend):
    """Partitioned inputs: owner-masked kernels match the host reducers
    per contributor part (each owned leaf counted exactly once)."""
    tree = random_tree(3)
    parts = partition_snapshot(tree.to_arrays(), "amr", 3)
    dag = ReducerDAG([
        SliceReducer(field="density", axis=2, position=0.5, resolution=32),
        ProjectionReducer(field="density", axis=2, resolution=32),
        LevelHistogramReducer(field="density", bins=16, lo=-8.0, hi=8.0),
    ])
    runner = DeviceDAGRunner(dag, backend=backend)
    for g, part in enumerate(parts):
        snap = Snapshot(step=0, kind="amr", arrays=part, domain=g,
                        n_domains=len(parts))
        assert_parity(dag, runner.run(snap), part, n_domains=len(parts),
                      domain=g)


def test_device_impl_registry_fallback_configs():
    """Unsupported configs resolve to None -> host fallback."""
    assert device_impl_for(SliceReducer(resolution=64)) is not None
    # non-power-of-two resolution: integer pixel geometry doesn't apply
    assert device_impl_for(SliceReducer(resolution=100)) is None
    # upstream source: runs on host from the upstream's output
    assert device_impl_for(
        SliceReducer(resolution=64, source="lod2")) is None
    # the LOD cut is a BFS prefix slice: device impl since PR 9
    assert device_impl_for(LODCutReducer(max_level=2)) is not None
    assert device_impl_for(ProjectionReducer(resolution=48)) is None
    assert device_impl_for(LevelHistogramReducer()) is not None


# ----------------------------------------------------------- device staging

def test_device_staging_holds_jax_arrays_and_copies():
    """Staged snapshots are device-resident; compute may mutate its host
    arrays right after push (the upload is a real copy)."""
    import jax
    st = DeviceStagingArea(capacity=2)
    a = np.arange(8.0)
    assert st.push(1, {"a": a})
    a[:] = -1.0
    snap = st.pop(timeout=1.0)
    assert isinstance(snap.arrays["a"], jax.Array)
    assert snap.arrays["a"].dtype == np.float32   # 32-bit device tables
    np.testing.assert_array_equal(np.asarray(snap.arrays["a"]),
                                  np.arange(8.0))
    st.release(snap)
    st.close()


def test_device_staging_survives_donated_device_arrays():
    """A jax-array push restages device-side (counted as reuse) and the
    staged copy survives deletion of the producer's buffer — the
    trainer's train step *donates* its state, which deletes the
    original while the snapshot is still queued."""
    import jax.numpy as jnp
    st = DeviceStagingArea(capacity=2)
    x = jnp.arange(16.0)
    assert st.push(1, {"x": x})
    assert st.stats.buffer_reuses == 1      # device-resident: no upload
    assert st.stats.buffer_allocs == 0
    x.delete()                              # what donation does
    snap = st.pop(timeout=1.0)
    np.testing.assert_array_equal(np.asarray(snap.arrays["x"]),
                                  np.arange(16.0))
    st.release(snap)
    st.close()


def test_device_staging_drop_oldest_parity():
    st = DeviceStagingArea(capacity=2, policy="drop-oldest")
    for s in range(1, 6):
        assert st.push(s, {"a": np.full(4, float(s))})
    assert len(st) == 2
    assert st.stats.evicted == 3
    snaps = [st.pop(timeout=1.0), st.pop(timeout=1.0)]
    assert [s.step for s in snaps] == [4, 5]
    for s in snaps:
        st.release(s)
    st.close()


# ------------------------------------------------------------- engine e2e

def test_engine_device_reduce_bit_identical(tmp_path):
    """device_reduce=True writes a catalog within the §18 contract of
    the host path (exact for histograms on the cast values), transfers
    less than the full snapshot, and never materializes a full snapshot
    on host (every default reducer has a device impl, the LOD cut
    included)."""
    tree = random_tree(11)
    mk = lambda: [  # noqa: E731
        SliceReducer(field="density", resolution=64),
        ProjectionReducer(field="density", resolution=64),
        LevelHistogramReducer(field="density", bins=16),
        LODCutReducer(max_level=2),
    ]
    roots = {}
    for mode in (False, True):
        root = str(tmp_path / f"db_{mode}")
        roots[mode] = root
        eng = InTransitEngine(root, mk(), device_reduce=mode).start()
        for s in (1, 2):
            assert eng.submit(s, tree)
        eng.close()
        if mode:
            ds = eng.device_stats
            assert ds["snapshots"] == 2
            assert not ds["fallback_runs"]
            assert ds["fallback_snapshots"] == 0
            assert 0 < ds["bytes_to_host"]
        else:
            assert eng.device_stats is None
    ch, cd = Catalog(roots[False]), Catalog(roots[True])
    dag = ReducerDAG(mk())
    cast = dag.run(Snapshot(step=0, kind="amr",
                            arrays=cast_like_device(tree.to_arrays())))
    for s in (1, 2):
        assert ch.reducers(s) == cd.reducers(s)
        for r in dag.order:
            err, limit = parity_check(r, cd.query(s, r.name),
                                      ch.query(s, r.name), cast[r.name])
            assert err <= limit, (r.name, err, limit)
    ch.close()
    cd.close()


def test_engine_device_reduce_transfer_savings(tmp_path):
    """Without host-fallback reducers, device->host traffic is a small
    fraction of the staged snapshot bytes (the subsystem's raison
    d'etre)."""
    tree = amrgen.generate_tree(fields.sedov(), min_level=3, max_level=6,
                                threshold=1.1)
    eng = InTransitEngine(str(tmp_path / "db"), [
        SliceReducer(field="density", resolution=32),
        ProjectionReducer(field="density", resolution=32),
        LevelHistogramReducer(field="density", bins=16, lo=-8.0, hi=8.0),
    ], device_reduce=True).start()
    assert eng.submit(1, tree)
    eng.close()
    ds = eng.device_stats
    staged = sum(a.stats.bytes_staged for a in eng.stages)
    assert ds["fallback_snapshots"] == 0
    assert ds["bytes_to_host"] < staged / 4
    assert Catalog(str(tmp_path / "db")).steps() == [1]


def test_engine_device_reduce_multidomain_merge(tmp_path):
    """device_reduce composes with contributor groups: per-domain device
    parts meet the §18 contract against the host multi-domain path, and
    the merged answers agree with the single-domain reference."""
    tree = random_tree(9)
    arrays = tree.to_arrays()
    mk = lambda: [  # noqa: E731
        ProjectionReducer(field="density", resolution=32),
        LevelHistogramReducer(field="density", bins=16, lo=-8.0, hi=8.0),
    ]
    roots = {}
    for name, domains, dev, src in (
            ("ref", 1, True, arrays), ("md_host", 2, False, arrays),
            ("md_cast", 2, False, cast_like_device(arrays)),
            ("md_dev", 2, True, arrays)):
        roots[name] = str(tmp_path / name)
        eng = InTransitEngine(roots[name], mk(), domains=domains,
                              device_reduce=dev).start()
        assert eng.submit(1, src)
        eng.close()
    cats = {name: Catalog(root) for name, root in roots.items()}
    ref, md_dev = cats["ref"], cats["md_dev"]
    pname, hname = "proj-density-ax2-r32", "hist-density-b16-lo-8-hi8"
    assert md_dev.domains(1, pname) == [0, 1]
    # device multi-domain vs host multi-domain under §18 (merged and per
    # domain): projection within rtol, histogram exact on cast values
    for r in ReducerDAG(mk()).order:
        for d in (None, 0, 1):
            q = {name: cats[name].query(1, r.name, domain=d)
                 for name in ("md_host", "md_cast", "md_dev")}
            err, limit = parity_check(r, q["md_dev"], q["md_host"],
                                      q["md_cast"])
            assert err <= limit, (r.name, d, err, limit)
    # and the merged answers recover the single-domain reference: the
    # projection to float32 roundoff of the single-domain device image
    # (same float32 tables; the sum-merge only reorders adds — 0.0 on
    # this tree) and within §18 of the float64 host image, histogram
    # counts exactly (padded rows aside, every leaf counted once)
    b = md_dev.query(1, pname)["image"]
    np.testing.assert_allclose(b, ref.query(1, pname)["image"], rtol=1e-6)
    np.testing.assert_allclose(b, cats["md_host"].query(1, pname)["image"],
                               rtol=1e-4)
    ha, hb = ref.query(1, hname)["hist"], md_dev.query(1, hname)["hist"]
    assert ha.sum() == hb.sum()
    rows = min(ha.shape[0], hb.shape[0])
    np.testing.assert_array_equal(ha[:rows], hb[:rows])
    for cat in cats.values():
        cat.close()


def test_engine_device_reduce_rejects_process_backend(tmp_path):
    with pytest.raises(ValueError, match="thread"):
        InTransitEngine(str(tmp_path / "db"),
                        [SliceReducer(resolution=32)],
                        device_reduce=True, backend="process")


@pytest.mark.parametrize("mode", [True, "mesh"])
def test_insitu_cli_flags_device_reducers_run_on_host(mode, tmp_path):
    """launch.insitu fails a run in which a reducer with a device impl
    ran on the host: at a non-pow2 resolution the slice and projection
    fall back; at a pow2 one only the source-chained slice runs on host
    and the run passes."""
    from repro.launch.insitu import main
    flag = ["--device-reduce"] if mode is True else ["--device-mesh", "1"]
    for resolution, rc in ((48, 1), (32, 0)):
        argv = flag + ["--out", str(tmp_path / f"r{resolution}"),
                       "--steps", "2", "--max-level", "4", "--lod", "2",
                       "--resolution", str(resolution), "--queries", "1",
                       "--policy", "block"]
        assert main(argv) == rc, resolution
