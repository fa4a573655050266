"""Lane runtime + server-mode catalog.

Covers: thread/process backend parity (same steps in, byte-identical
merged reads out), shared-memory slab reclamation on release()/close(),
TTL-finalized partial contexts, and a RemoteCatalog round trip against a
live catalog server on an ephemeral port.
"""
import pickle
import time

import numpy as np
import pytest

from repro.ckpt import AsyncCheckpointManager
from repro.insitu import (Catalog, CatalogServer, InTransitEngine,
                          LevelHistogramReducer, LODCutReducer,
                          ProjectionReducer, RemoteCatalog, ShmStagingArea,
                          SliceReducer, TensorNormReducer)
from repro.insitu.lanes import MODES
from repro.insitu.partition import partition_snapshot
from repro.insitu.staging import _attach_shm
from repro.sim import amrgen, fields


@pytest.fixture(scope="module")
def sedov_tree():
    t = amrgen.generate_tree(fields.sedov(), min_level=2, max_level=5,
                             threshold=1.2)
    t.validate()
    return t


def _reducers():
    # fixed histogram bounds: auto bounds cannot merge across domains
    return [LODCutReducer(max_level=3),
            SliceReducer(field="density", axis=2, position=0.5,
                         resolution=48),
            ProjectionReducer(field="density", axis=2, resolution=48),
            LevelHistogramReducer(field="density", bins=16, lo=0.0, hi=8.0)]


# ----------------------------------------------------- backend registry

_OWNERS = {
    "engine": lambda root, mode: InTransitEngine(root, [], backend=mode),
    "manager": lambda root, mode: AsyncCheckpointManager(
        root, lane_backend=mode),
}


@pytest.mark.parametrize("owner", sorted(_OWNERS))
def test_backend_registry(tmp_path, owner):
    """Both lane owners take exactly the runtime's two modes, and refuse
    any other name before touching the disk."""
    make = _OWNERS[owner]
    assert MODES == ("thread", "process")
    for mode in MODES:
        obj = make(str(tmp_path / mode), mode)
        assert obj._backend.mode == mode
        obj.close()
    root = tmp_path / "db"
    with pytest.raises(ValueError,
                       match="unknown lane backend 'warp-drive'.*"
                             "'thread', 'process'"):
        make(str(root), "warp-drive")
    assert not root.exists()   # validated before touching the disk


# ----------------------------------------------- thread/process parity

def test_thread_process_parity_byte_identical(tmp_path, sedov_tree):
    """Same steps in -> byte-identical merged reads out of either lane
    runtime, and identical context attrs surface (the acceptance bar:
    thread stays PR-3 behavior, process reproduces it exactly)."""
    roots = {}
    for backend in ("thread", "process"):
        root = str(tmp_path / backend)
        roots[backend] = root
        eng = InTransitEngine(root, _reducers(), domains=2,
                              backend=backend, policy="block",
                              queue_capacity=2).start()
        assert eng.backend == backend
        for s in (1, 2):
            assert eng.submit(s, sedov_tree)
        eng.close()
        assert eng.written_steps == [1, 2]

    ct, cp = Catalog(roots["thread"]), Catalog(roots["process"])
    assert ct.steps() == cp.steps() == [1, 2]
    checked = 0
    for s in ct.steps():
        assert ct.reducers(s) == cp.reducers(s)
        at, ap = ct.attrs(s)["insitu"], cp.attrs(s)["insitu"]
        for key in ("kind", "reducers", "merge", "n_domains", "domains"):
            assert at[key] == ap[key], key
        for reducer in ct.reducers(s):
            assert ct.domains(s, reducer) == cp.domains(s, reducer) == [0, 1]
            merged_t = ct.query(s, reducer)            # merge-at-read
            merged_p = cp.query(s, reducer)
            assert set(merged_t) == set(merged_p)
            for k, v in merged_t.items():
                assert v.dtype == merged_p[k].dtype
                assert np.array_equal(v, merged_p[k], equal_nan=True), \
                    (s, reducer, k)
                checked += 1
            for d in (0, 1):                           # per-domain parts
                pt, pp = ct.query(s, reducer, domain=d), \
                    cp.query(s, reducer, domain=d)
                for k, v in pt.items():
                    assert np.array_equal(v, pp[k], equal_nan=True)
    assert checked >= 8
    ct.close()
    cp.close()


def test_process_backend_forces_exclusive_groups(tmp_path):
    from repro.hercule.database import HerculeDB
    # engine-created db: ncf forced to 1 so each lane owns its files
    eng = InTransitEngine(str(tmp_path / "a"), _reducers(), domains=2,
                          backend="process")
    assert eng.db.ncf == 1
    eng.close(drain=False)
    # pre-opened db with shared group files is refused
    db = HerculeDB.create(str(tmp_path / "b"), kind="hdep", ncf=4)
    with pytest.raises(ValueError, match="ncf"):
        InTransitEngine(db, _reducers(), domains=2, backend="process")
    db.close()
    # a *pre-existing* ncf=4 database directory is refused too: create()
    # honors the on-disk manifest, so the parent and the lane processes
    # can never disagree about the group->file mapping
    with pytest.raises(ValueError, match="ncf"):
        InTransitEngine(str(tmp_path / "b"), _reducers(), domains=2,
                        backend="process")


def test_create_honors_existing_manifest(tmp_path):
    """HerculeDB.create on an existing database adopts the on-disk
    manifest — the files were laid out under *that* ncf — instead of
    silently returning a handle with the requested parameters."""
    from repro.hercule.database import HerculeDB
    HerculeDB.create(str(tmp_path / "db"), kind="hdep", ncf=4).close()
    again = HerculeDB.create(str(tmp_path / "db"), kind="hdep", ncf=1)
    assert again.ncf == 4
    again.close()


# --------------------------------------------------- shm slab lifecycle

def test_shm_slab_reclamation_on_release_and_close():
    area = ShmStagingArea(capacity=2, policy="block", n_slots=3)
    consumer = ShmStagingArea.attach(area.handle())

    assert area.push(1, {"a": np.arange(64.0)})
    assert area.push(2, {"a": np.arange(64.0) * 2})
    assert len(area) == 2
    snap = consumer.pop(timeout=1.0)
    np.testing.assert_array_equal(snap.arrays["a"], np.arange(64.0))

    # release() returns the slab to the ring: the same slot (same shm
    # segment generation) is reused by the next push, no new allocation
    allocs_before = area.stats.buffer_allocs
    consumer.release(snap)
    assert snap._slot is None            # double-release is a no-op
    assert area.push(3, {"a": np.arange(64.0) * 3})
    assert area.stats.buffer_allocs == allocs_before
    assert area.stats.buffer_reuses >= 1

    # growth: an oversized snapshot rolls the slab to a new generation
    for _ in range(2):
        consumer.release(consumer.pop(timeout=1.0))
    assert area.push(4, {"big": np.zeros(200_000)})
    big = consumer.pop(timeout=1.0)
    assert big.arrays["big"].nbytes == 1_600_000
    consumer.release(big)

    # close() + unlink() reclaim every named segment
    names = [area._data_name(slot, gen)
             for slot, (gen, _) in area._segs.items()]
    names.append(area._shm.name)
    area.close()
    assert consumer.pop(timeout=0.5) is None and consumer.closed
    consumer.detach()
    area.unlink()
    for name in names:
        with pytest.raises(FileNotFoundError):
            _attach_shm(name)


def test_shm_shared_stride_controller_multi_producer():
    """Subsample stride state lives in the segment's control words: every
    bound producer sees one consistent stride, and overflow driven from
    one binding moves the stride observed through another."""
    from repro.insitu.staging import SharedStrideController

    area = ShmStagingArea(capacity=2, policy="subsample", n_slots=3)
    peer = ShmStagingArea.attach(area.handle())
    assert isinstance(area._ctrl, SharedStrideController)
    assert area.stride == 1 and peer.stride == 1

    # sustained overflow through the *peer* binding raises the stride
    # the owner observes (one shared controller, not one per producer)
    for _ in range(64):
        peer._ctrl.overflow()
    assert peer.stride > 1
    assert area.stride == peer.stride

    # the shared decision function gates pushes identically on both ends
    stride = area.stride
    accepted = sum(bool(area.push(s, {"a": np.zeros(4)}))
                   for s in range(stride * 2))
    assert accepted == 2          # one admit per stride cycle, 2 cycles

    # freeze() on detach keeps a coherent host-side copy after unlink
    peer.detach()
    assert peer.stride == stride
    while len(area):
        area.release(area.pop(timeout=1.0))
    area.close()
    area.unlink()
    assert area.stride == stride  # frozen plain controller survives


def test_shm_area_policies_match_thread_semantics():
    """drop-oldest keeps the freshest snapshots; victims fire on_evict."""
    evicted = []
    area = ShmStagingArea(capacity=2, policy="drop-oldest", n_slots=3,
                          on_evict=evicted.append)
    for s in range(1, 6):
        assert area.push(s, {"a": np.full(4, float(s))})
    assert len(area) == 2
    assert area.stats.evicted == 3
    assert [v.step for v in evicted] == [1, 2, 3]
    got = [area.pop(timeout=1.0), area.pop(timeout=1.0)]
    assert [g.step for g in got] == [4, 5]
    np.testing.assert_array_equal(got[1].arrays["a"], np.full(4, 5.0))
    for g in got:
        area.release(g)
    area.close()
    area.unlink()


# ------------------------------------------------- TTL partial contexts

def test_step_ttl_finalizes_partial_context(tmp_path, sedov_tree):
    """A producer skipping an on-cadence step no longer leaks the
    pending context: after step_ttl the context commits with the
    surviving domains only (same path as drop-oldest eviction)."""
    eng = InTransitEngine(str(tmp_path / "db"), _reducers(), domains=2,
                          step_ttl=0.25).start()
    parts = partition_snapshot(sedov_tree.to_arrays(), "amr", 2)
    assert eng.submit_part(1, 0, parts[0])   # producer 1 never shows up
    eng.drain(timeout=15.0)
    assert eng.ttl_expired_steps == 1
    # a healthy step afterwards still completes with both domains
    assert eng.submit_part(2, 0, parts[0])
    assert eng.submit_part(2, 1, parts[1])
    eng.close()

    cat = Catalog(str(tmp_path / "db"))
    assert cat.steps() == [1, 2]
    assert cat.attrs(1)["insitu"]["domains"] == [0]
    assert cat.attrs(2)["insitu"]["domains"] == [0, 1]
    # the partial context serves its surviving domain transparently
    hist = cat.query(1, "hist-density-b16-lo0-hi8")["hist"]
    part = cat.query(1, "hist-density-b16-lo0-hi8", domain=0)["hist"]
    np.testing.assert_array_equal(hist, part)
    cat.close()


def test_step_ttl_late_straggler_cannot_overwrite_manifest(tmp_path,
                                                           sedov_tree):
    """A part arriving after its step's context TTL-committed is
    rejected: a lone straggler restarting the countdown would commit a
    manifest holding only its own domain over the survivors'."""
    eng = InTransitEngine(str(tmp_path / "db"), _reducers(), domains=2,
                          step_ttl=0.25).start()
    parts = partition_snapshot(sedov_tree.to_arrays(), "amr", 2)
    assert eng.submit_part(1, 0, parts[0])
    eng.drain(timeout=15.0)            # TTL commits with domains=[0]
    assert eng.submit_part(1, 1, parts[1]) is False   # straggler rejected
    eng.close()
    cat = Catalog(str(tmp_path / "db"))
    assert cat.attrs(1)["insitu"]["domains"] == [0]   # manifest intact
    cat.close()


def test_step_ttl_all_parts_skipped_leaves_no_context(tmp_path):
    """TTL on a step where nothing landed: no empty context litter."""
    eng = InTransitEngine(str(tmp_path / "db"),
                          [LevelHistogramReducer()], domains=2,
                          step_ttl=0.2).start()
    # a part of an unknown kind settles as 'skipped'; the other producer
    # never submits -> countdown completes via TTL with ctx=None
    assert eng.submit_part(1, 0, {"x": np.zeros(8)}, kind="tensors")
    eng.drain(timeout=15.0)
    eng.close()
    assert eng.ttl_expired_steps == 1
    assert Catalog(str(tmp_path / "db")).steps() == []


# --------------------------------------------- remote catalog round trip

def test_remote_catalog_round_trip(tmp_path, sedov_tree):
    """RemoteCatalog over a live ephemeral-port server returns arrays
    equal to the local merge-at-read for a 2-domain run."""
    root = str(tmp_path / "db")
    eng = InTransitEngine(root, _reducers(), domains=2).start()
    for s in (1, 2, 3):
        assert eng.submit(s, sedov_tree)
    eng.close()

    local = Catalog(root)
    srv = CatalogServer(local, port=0).start()
    try:
        rc = RemoteCatalog(srv.url)
        assert rc.steps() == local.steps() == [1, 2, 3]
        assert rc.latest_step() == 3
        assert rc.reducers(3) == local.reducers(3)
        assert rc.attrs(3)["insitu"]["domains"] == [0, 1]

        for reducer in rc.reducers(3):
            assert rc.domains(3, reducer) == local.domains(3, reducer)
            remote = rc.query(3, reducer)        # server-side merge
            ref = local.query(3, reducer)
            assert set(remote) == set(ref)
            for k, v in ref.items():
                assert remote[k].dtype == v.dtype
                assert np.array_equal(v, remote[k], equal_nan=True), \
                    (reducer, k)
            one = rc.query(3, reducer, domain=1)  # concrete domain part
            for k, v in local.query(3, reducer, domain=1).items():
                assert np.array_equal(v, one[k], equal_nan=True)

        # region crops are applied server-side on the cached object
        slicer = next(r for r in rc.reducers(3) if r.startswith("slice"))
        win = rc.query(3, slicer, region=((8, 24), (4, 20)))["image"]
        np.testing.assert_array_equal(
            win, local.query(3, slicer)["image"][8:24, 4:20])

        # series mirrors Catalog.series (steps + per-step arrays)
        st, vals = rc.series(slicer, "image")
        lst, lvals = local.series(slicer, "image")
        np.testing.assert_array_equal(st, lst)
        assert all(np.array_equal(a, b, equal_nan=True)
                   for a, b in zip(vals, lvals))

        # many viewers, one cache: this viewer's repeated query now
        # revalidates client-side (304, zero payload)...
        before_etag = rc.client_cache_info()["etag_hits"]
        rc.query(3, slicer)
        assert rc.client_cache_info()["etag_hits"] > before_etag
        # ...while a *fresh* viewer (empty ETag cache) still shares the
        # server's LRU reduction cache
        before = rc.cache_info()
        RemoteCatalog(srv.url).query(3, slicer)
        after = rc.cache_info()
        assert after["hits"] > before["hits"]

        # a missing object raises KeyError exactly like the local catalog
        with pytest.raises(KeyError):
            rc.query(3, "absent-reducer")
        with pytest.raises(KeyError):
            rc.reducers(99)
    finally:
        srv.close()
        local.close()


# ------------------------------------------------------ reducer pickling

def test_jitted_reducers_pickle_for_process_lanes():
    """Process lanes ship reducers to spawned workers: the jitted
    closures drop out of the pickle and rebuild on arrival."""
    r = TensorNormReducer()
    clone = pickle.loads(pickle.dumps(r))
    assert clone.name == r.name and clone.merge == r.merge
    from repro.insitu.staging import Snapshot
    snap = Snapshot(step=0, kind="tensors",
                    arrays={"w": np.arange(12.0).reshape(3, 4)})
    out = clone.reduce(snap, {})
    np.testing.assert_allclose(
        out["stats"][0, 0], np.linalg.norm(np.arange(12.0)), rtol=1e-6)


def test_drain_timeout_still_raises(tmp_path):
    """Without a TTL, a skipped producer surfaces as a drain timeout
    (the PR-3 contract) rather than silently committing."""
    eng = InTransitEngine(str(tmp_path / "db"), _reducers(), domains=2).start()
    t = amrgen.generate_tree(fields.sedov(), min_level=2, max_level=3,
                             threshold=1.2)
    parts = partition_snapshot(t.to_arrays(), "amr", 2)
    assert eng.submit_part(1, 0, parts[0])
    time.sleep(0.1)
    with pytest.raises(TimeoutError):
        eng.drain(timeout=0.5)
    # the missing part arrives late: everything completes after all
    assert eng.submit_part(1, 1, parts[1])
    eng.close()
    assert eng.written_steps == [1]


# ------------------------------------------------- server auth + ETag

def _insitu_db(tmp_path, sedov_tree):
    root = str(tmp_path / "srvdb")
    eng = InTransitEngine(root, _reducers(), domains=2).start()
    for s in (1, 2):
        eng.submit(s, sedov_tree)
    eng.close()
    return root


def test_server_bearer_token_auth(tmp_path, sedov_tree):
    """--token mode: requests without the exact bearer token get 401
    (PermissionError client-side); the right token is served normally."""
    root = _insitu_db(tmp_path, sedov_tree)
    srv = CatalogServer(root, port=0, token="s3cret").start()
    try:
        with pytest.raises(PermissionError):
            RemoteCatalog(srv.url).steps()
        with pytest.raises(PermissionError):
            RemoteCatalog(srv.url, token="wrong").steps()
        rc = RemoteCatalog(srv.url, token="s3cret")
        assert rc.steps() == [1, 2]
        assert rc.query(1, _reducers()[2].name)["image"].shape == (48, 48)
    finally:
        srv.close()


def test_remote_catalog_etag_cache(tmp_path, sedov_tree):
    """Hot viewers skip the transfer: a repeated query revalidates via
    If-None-Match, gets a 304, and serves the cached arrays."""
    root = _insitu_db(tmp_path, sedov_tree)
    srv = CatalogServer(root, port=0).start()
    try:
        rc = RemoteCatalog(srv.url)
        name = _reducers()[2].name
        first = rc.query(1, name)
        assert rc.client_cache_info() == {"entries": 1, "etag_hits": 0,
                                          "etag_misses": 1}
        again = rc.query(1, name)
        info = rc.client_cache_info()
        assert info["etag_hits"] == 1 and info["etag_misses"] == 1
        np.testing.assert_array_equal(first["image"], again["image"])
        # cached arrays are frozen like the local catalog's
        with pytest.raises(ValueError):
            again["image"][0, 0] = 1.0
        # distinct (region/domain) keys are separate cache entries
        crop = rc.query(1, name, region=((0, 8), (0, 8)))
        assert crop["image"].shape == (8, 8)
        dom = rc.query(1, name, domain=0)
        assert rc.client_cache_info()["entries"] == 3
        np.testing.assert_array_equal(crop["image"], first["image"][:8, :8])
        # and revalidation still matches a fresh unconditional fetch
        fresh = RemoteCatalog(srv.url).query(1, name, domain=0)
        np.testing.assert_array_equal(dom["image"], fresh["image"])
    finally:
        srv.close()


def test_etag_rotates_and_cache_invalidates_on_context_rewrite(tmp_path,
                                                               sedov_tree):
    """A rewritten context (engine resubmission) must rotate the ETag
    AND drop the server's cached bytes — a fresh validator stamped onto
    stale LRU content would poison every client forever."""
    from repro.hercule import api
    from repro.hercule.database import HerculeDB
    root = str(tmp_path / "db")
    db = HerculeDB.create(root, kind="hdep", ncf=1)
    ctx = db.begin_context(1)
    api.write_object(ctx, "reduced", 0, {"x": np.zeros(8)}, reducer="red")
    ctx.finalize(attrs={"insitu": {"reducers": ["red"], "merge": {},
                                   "n_domains": 1, "domains": [0]}})
    srv = CatalogServer(root, port=0).start()
    try:
        rc = RemoteCatalog(srv.url)
        np.testing.assert_array_equal(rc.query(1, "red")["x"], np.zeros(8))
        # rewrite step 1 with different bytes (and a changed manifest)
        time.sleep(0.01)          # ensure a distinct mtime_ns
        ctx = db.begin_context(1)
        api.write_object(ctx, "reduced", 0, {"x": np.ones(8)},
                         reducer="red")
        ctx.finalize(attrs={"insitu": {"reducers": ["red"], "merge": {},
                                       "n_domains": 1, "domains": [0]}})
        # revalidation must MISS (rotated tag) and serve the new bytes
        out = rc.query(1, "red")["x"]
        np.testing.assert_array_equal(out, np.ones(8))
        assert rc.client_cache_info()["etag_misses"] == 2
        # and the fresh tag now revalidates to the fresh bytes
        np.testing.assert_array_equal(rc.query(1, "red")["x"], np.ones(8))
        assert rc.client_cache_info()["etag_hits"] == 1
    finally:
        srv.close()
        db.close()
