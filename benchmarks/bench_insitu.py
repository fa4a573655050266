"""In-transit engine: compute-loop overhead (engine on vs off),
reduction-query throughput vs post-hoc assembly, multi-domain
contributor-group scaling with merge-at-read verification, and the
device-reduce transfer ratio (staged-on-accelerator reduction vs the
host path's full-snapshot device→host copy).

The paper's argument in numbers: a viewer hitting the reduced catalog
should beat re-assembling the global tree from full HDep objects by a
large factor, the compute flow should pay ~nothing for staging, and
per-producer reduction+write should scale with contributor groups while
merged reads return exactly the single-producer answer.

The multi-domain mode emulates the paper's producers with OS processes
(one per contributor group, like MPI ranks — threads would share the
GIL and measure the interpreter, not the I/O path): each producer runs
the reducer DAG on its own partition and lands its reduced objects as
its own Hercule domain; the parent commits one manifest per context and
verifies a 4-domain ``read_merged`` against the 1-domain reference.
Workers are spawned (not forked — earlier bench modules may hold live
XLA/pool threads) and receive the partitions once, at pool startup,
outside every timed region.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import shutil
import time

import numpy as np

from repro.hercule import HerculeDB, analysis, api
from repro.hercule.database import Record
from repro.insitu import Catalog, InTransitEngine, SliceReducer

from .common import emit, orion_domains, scratch_dir, timeit

RESOLUTION = 256

# ---------------------------------------------------- multi-domain mode

GROUPS = (1, 2, 4)
MD_STEPS = 5
MD_REPS = 6     # best-of: rides out noisy-neighbor CPU steal windows

#: per-producer state installed into each spawned worker by _md_init
_MD: dict = {}


def _md_init(roots: dict, parts: dict) -> None:
    from repro.runtime import pin_to_cpu
    pin_to_cpu()                  # the bench process may hold the chip
    _MD["roots"], _MD["parts"] = roots, parts


def _md_reducers():
    from repro.insitu import (LevelHistogramReducer, LODCutReducer,
                              ProjectionReducer)
    # fixed histogram bounds: per-partition auto bounds cannot merge
    return [LODCutReducer(max_level=12),
            ProjectionReducer(field="density", resolution=RESOLUTION),
            LevelHistogramReducer(field="density", bins=64,
                                  lo=0.0, hi=50.0)]


def _md_land(args):
    """One producer's task: reduce + write its domain for a step batch."""
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot
    n_groups, g, steps = args
    root, parts = _MD["roots"][n_groups], _MD["parts"][n_groups]
    dag = ReducerDAG(_md_reducers())
    db = HerculeDB.open(root)
    out = []
    for step in steps:
        outputs = dag.run(Snapshot(step=step, kind="amr", arrays=parts[g],
                                   domain=g, n_domains=n_groups))
        ctx = db.begin_context(step)
        for rname, arrays in outputs.items():
            api.write_object(ctx, "reduced", g, arrays, reducer=rname,
                             compress=False)
        out.append((step, [r.to_json() for r in ctx.records]))
        ctx.abort()   # records travel back to the parent, which commits
    db.close()
    return out


def _md_commit(root: str, results, merge_map: dict) -> None:
    """Commit one manifest per context from the producers' records."""
    by_step: dict[int, list] = {}
    for batch in results:
        for step, recs in batch:
            by_step.setdefault(step, []).extend(recs)
    db = HerculeDB.open(root)
    for step, recs in sorted(by_step.items()):
        ctx = db.begin_context(step)
        ctx.records.extend(Record.from_json(r) for r in recs)
        ctx.finalize(attrs={"insitu": {
            "merge": merge_map, "n_domains": len(results),
            "domains": sorted({r["domain"] for r in recs})}})
    db.close()


def run_multidomain() -> float:
    """Contributor-group scaling + merge-at-read equality. Returns the
    4-group vs 1-group write-throughput ratio."""
    tree, _, _ = orion_domains(16)
    merge_map = {r.name: r.merge for r in _md_reducers()}
    roots, parts_by_n, part_ms = {}, {}, {}
    for n in GROUPS:
        t0 = time.perf_counter()
        from repro.insitu.partition import partition_snapshot
        parts_by_n[n] = partition_snapshot(tree.to_arrays(), "amr", n)
        part_ms[n] = (time.perf_counter() - t0) * 1e3
        roots[n] = scratch_dir(f"hx_bench_md{n}_")
        HerculeDB.create(roots[n], kind="hdep", ncf=1)
    emit("insitu.partition_g4", part_ms[4] * 1e3,
         f"hilbert split+closure into 4 groups, "
         f"{tree.n_nodes} nodes", unit="us_per_call", repeats=1)

    # one OS process per producer, capped at the cores we actually have
    procs = min(max(GROUPS), os.cpu_count() or 1)
    best = {n: float("inf") for n in GROUPS}
    with mp.get_context("spawn").Pool(processes=procs, initializer=_md_init,
                                      initargs=(roots, parts_by_n)) as pool:
        for n in GROUPS:   # warm page caches, allocators, imports
            pool.map(_md_land, [(n, g, [0]) for g in range(n)])
        for rep in range(MD_REPS):      # interleave G's so drift hits all
            for n in GROUPS:
                steps = [1000 * rep + s for s in range(1, MD_STEPS + 1)]
                t0 = time.perf_counter()
                results = pool.map(_md_land,
                                   [(n, g, steps) for g in range(n)])
                best[n] = min(best[n], time.perf_counter() - t0)
                if rep == 0:
                    _md_commit(roots[n], results, merge_map)

    nbytes = {}
    for n in GROUPS:
        db = HerculeDB.open(roots[n])
        nbytes[n] = sum(sum(r.nbytes for r in db.view(s).records)
                        for s in range(1, MD_STEPS + 1))
        db.close()
    thr = {n: nbytes[n] / best[n] for n in GROUPS}
    for n in GROUPS:
        emit(f"insitu.multidomain_write_g{n}",
             best[n] / MD_STEPS * 1e6,
             f"{thr[n]/1e6:.0f}MB/s reduce+write scaling="
             f"{thr[n]/thr[1]:.2f}x producers={min(n, procs)}proc "
             f"{nbytes[n]/MD_STEPS/1e6:.1f}MB/ctx",
             repeats=MD_REPS)

    # merge-at-read: the 4-domain merged object must equal the 1-domain
    # reference (counts exactly; float images to fp-roundoff)
    cat1, cat4 = Catalog(roots[1]), Catalog(roots[4])
    checked = mismatched = 0
    t0 = time.perf_counter()
    for reducer in cat1.reducers(1):
        ref, merged = cat1.query(1, reducer), cat4.query(1, reducer)
        for k, a in ref.items():
            b = merged[k]
            checked += 1
            ok = np.array_equal(a, b, equal_nan=True) if a.dtype.kind != "f" \
                else bool(np.allclose(a, b, equal_nan=True, rtol=1e-12,
                                      atol=0) or np.array_equal(
                              a, b, equal_nan=True))
            if not ok:
                mismatched += 1
    t_merge = time.perf_counter() - t0
    emit("insitu.read_merged_g4", t_merge * 1e6,
         f"arrays_checked={checked} mismatched={mismatched} "
         f"domains={cat4.domains(1, cat4.reducers(1)[0])}", repeats=1)
    cat1.db.close()
    cat4.db.close()
    for root in roots.values():
        shutil.rmtree(root, ignore_errors=True)
    if mismatched:
        raise AssertionError(
            f"merge-at-read mismatch: {mismatched}/{checked} arrays")
    return thr[4] / thr[1]


# --------------------------------------------------- device-reduce mode

DEVICE_STEPS = 3
DEVICE_REPS = 3
DEVICE_MAX_LEVEL = 9     # a deeper tree: full snapshots are ~43 MB/step


def run_device() -> float:
    """Device-resident staging + on-device reduction vs the host path.

    Both engines run the 512-res reduction-bound DAG
    (:func:`_live_reducers`) on identical snapshots of a deep Orion
    tree. The host path stages every snapshot through a device→host
    full-resolution copy before reducing; the device path
    (``device_reduce=True``) stages on the accelerator and transfers
    only the reduced objects, accounted by the engine's
    ``device_stats``. Records the per-step bytes of both paths plus
    their ratio (``insitu.device_transfer_ratio``, acceptance floor 5x)
    and verifies the device catalog against the host one under the
    DESIGN §18 contract. Returns the transfer ratio.
    """
    from repro.insitu.device import cast_like_device, parity_check
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot
    tree, _, _ = orion_domains(16, max_level=DEVICE_MAX_LEVEL)
    arrays = tree.to_arrays()
    snap_bytes = sum(v.nbytes for v in arrays.values())

    roots, times, bytes_per_step = {}, {}, {}
    for mode in ("host", "device"):
        root = scratch_dir(f"hx_bench_dev_{mode}_")
        roots[mode] = root
        eng = InTransitEngine(root, _live_reducers(), policy="block",
                              queue_capacity=4,
                              device_reduce=(mode == "device")).start()
        eng.submit(DEVICE_STEPS + 1, arrays)      # warm lanes/compiles
        eng.drain(timeout=300.0)
        best, step = float("inf"), DEVICE_STEPS + 1
        for _ in range(DEVICE_REPS):
            t0 = time.perf_counter()
            for _ in range(DEVICE_STEPS):
                step += 1
                eng.submit(step, arrays)
            eng.drain(timeout=300.0)
            best = min(best, time.perf_counter() - t0)
        times[mode] = best
        n_steps = len(eng.written_steps)
        if mode == "device":
            ds = eng.device_stats
            bytes_per_step[mode] = ds["bytes_to_host"] / max(1, n_steps)
            assert not ds["fallback_runs"], ds   # all three run on device
        else:
            stats = eng.staging.stats
            bytes_per_step[mode] = stats.bytes_staged / max(1, n_steps)
        eng.close()

    # correctness: the device catalog meets the DESIGN §18 contract
    cat_h, cat_d = Catalog(roots["host"]), Catalog(roots["device"])
    step = cat_h.steps()[-1]
    dag = ReducerDAG(_live_reducers())
    cast = dag.run(Snapshot(step=step, kind="amr",
                            arrays=cast_like_device(arrays)))
    checked = mismatched = 0
    for r in dag.order:
        checked += 1
        err, limit = parity_check(r, cat_d.query(step, r.name),
                                  cat_h.query(step, r.name), cast[r.name])
        mismatched += not err <= limit
    cat_h.db.close()
    cat_d.db.close()
    for root in roots.values():
        shutil.rmtree(root, ignore_errors=True)
    if mismatched:
        raise AssertionError(
            f"device-reduce mismatch: {mismatched}/{checked} arrays")

    ratio = bytes_per_step["host"] / bytes_per_step["device"]
    emit("insitu.device_bytes_transferred", bytes_per_step["device"],
         f"device->host per step (reduced objects only), snapshot="
         f"{snap_bytes/1e6:.1f}MB, objects_checked={checked} "
         f"mismatched={mismatched}", unit="bytes_per_step",
         repeats=DEVICE_REPS)
    emit("insitu.host_bytes_transferred", bytes_per_step["host"],
         "host-path staging: full snapshot crosses per step",
         unit="bytes_per_step", repeats=DEVICE_REPS)
    emit("insitu.device_transfer_ratio", ratio,
         f"host full-snapshot / device reduced bytes per step "
         f"(acceptance floor 5x), 512-res DAG on "
         f"{tree.n_nodes} nodes", unit="x", repeats=DEVICE_REPS)
    emit("insitu.device_reduce_step", times["device"] / DEVICE_STEPS * 1e6,
         f"{snap_bytes * DEVICE_STEPS / times['device'] / 1e6:.0f}MB/s "
         f"device reduce throughput vs host "
         f"{snap_bytes * DEVICE_STEPS / times['host'] / 1e6:.0f}MB/s "
         f"(host step {times['host']/DEVICE_STEPS*1e6:.0f}us)",
         repeats=DEVICE_REPS)
    return ratio


# --------------------------------------------------- sharded mesh mode

MESH_DEVICES = 4
MESH_REPS = 3


def _mesh_measure() -> dict:
    """Mesh-vs-single reduce on every visible device (>= MESH_DEVICES).

    Runs the 512-res reduction DAG on the deep Orion tree through
    ``MeshDAGRunner`` at 1 device and at the full mesh and checks every
    output against the host reducers under the DESIGN §18 contract.
    """
    import jax

    from repro.insitu.device import cast_like_device, parity_check
    from repro.insitu.mesh_reduce import MeshDAGRunner
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot

    ndev = len(jax.devices())
    tree, _, _ = orion_domains(16, max_level=DEVICE_MAX_LEVEL)
    arrays = tree.to_arrays()
    snap = Snapshot(step=0, kind="amr", arrays=arrays)
    dag = ReducerDAG(_live_reducers())
    host = dag.run(snap)
    cast = dag.run(Snapshot(step=0, kind="amr",
                            arrays=cast_like_device(arrays)))
    out = {}
    for devices in (1, ndev):
        runner = MeshDAGRunner(dag, devices=devices)
        res = runner.run(snap)                 # warm compiles + upload
        best = float("inf")
        for _ in range(MESH_REPS):
            t0 = time.perf_counter()
            res = runner.run(snap)
            best = min(best, time.perf_counter() - t0)
        checked = mismatched = 0
        for r in dag.order:
            err, limit = parity_check(r, res[r.name], host[r.name],
                                      cast[r.name])
            checked += 1
            mismatched += not err <= limit
        st = runner.stats.as_dict()
        out[str(devices)] = {
            "t": best, "checked": checked, "mismatched": mismatched,
            "peak_leaf_frac": st["peak_leaf_frac"],
            "leaf_rows": st["leaf_rows"],
            "peak_table_mb": st["peak_device_table_bytes"] / 1e6,
            "fallback_snapshots": st["fallback_snapshots"]}
    return out


def _mesh_child() -> None:
    """Child body for :func:`run_mesh` on a CPU host (fresh process: the
    forced host device count must be in XLA_FLAGS before jax
    initializes); prints one tagged JSON line the parent parses."""
    import json
    print("MESH-JSON " + json.dumps(_mesh_measure()), flush=True)


def run_mesh() -> float | None:
    """Sharded multi-device reduction vs the single-device path.

    With at least ``MESH_DEVICES`` devices (a four-chip host) the
    measurement runs in this process, which already holds them. On a
    CPU host it spawns a child with ``XLA_FLAGS=--xla_force_host_
    platform_device_count=4`` (the flag must precede jax init, hence the
    subprocess). An accelerator host with fewer devices records nothing:
    a CPU child there would silently measure the wrong device. Records
    per-device leaf-table residency (``insitu.mesh_peak_leaf_frac``, CI
    ceiling 0.6 — the proof that no device ever holds more than ~1/N of
    the leaf table) and the mesh-vs-single wall-time ratio. Returns the
    residency fraction, or None when not measured.
    """
    import json
    import subprocess
    import sys

    import jax

    if len(jax.devices()) >= MESH_DEVICES:
        data = _mesh_measure()
    elif jax.default_backend() == "cpu":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=%d"
                            % MESH_DEVICES,
               "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   p for p in (os.path.join(root, "src"), root,
                               os.environ.get("PYTHONPATH")) if p)}
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_insitu",
             "--mesh-child"],
            env=env, cwd=root, capture_output=True, text=True,
            timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"mesh bench child failed:\n{proc.stderr[-3000:]}")
        line = next(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("MESH-JSON "))
        data = json.loads(line[len("MESH-JSON "):])
    else:
        print(f"# run_mesh: not measured ({len(jax.devices())} "
              f"{jax.default_backend()} device(s), needs {MESH_DEVICES})")
        return None
    bad = {k: v for k, v in data.items()
           if v["mismatched"] or v["fallback_snapshots"]}
    if bad:
        raise AssertionError(f"mesh parity/fallback failure: {bad}")
    devices = jax.devices()
    single, mesh = data["1"], data[max(data, key=int)]
    frac = mesh["peak_leaf_frac"]
    ratio = single["t"] / mesh["t"]
    where = (f"{MESH_DEVICES} forced host devices" if len(devices) <
             MESH_DEVICES else f"{len(devices)} {devices[0].device_kind}")
    emit("insitu.mesh_peak_leaf_frac", frac,
         f"per-device leaf-table residency at {where} "
         f"({mesh['leaf_rows']} leaf rows, "
         f"{mesh['peak_table_mb']:.1f}MB/device table), 512-res DAG, "
         f"objects_checked={mesh['checked']} mismatched=0 (ceiling 0.6)",
         unit="frac", repeats=MESH_REPS)
    emit("insitu.mesh_vs_single_x", ratio,
         f"single {single['t']*1e3:.0f}ms vs mesh "
         f"{mesh['t']*1e3:.0f}ms per snapshot on {where} (forced host "
         f"devices timeshare one CPU: there it documents shard_map+merge "
         f"overhead, not parallel speedup)", unit="x", repeats=MESH_REPS)
    emit("insitu.mesh_reduce_step", mesh["t"] * 1e6,
         f"shard_map reduce wall per snapshot on {where}, "
         f"merges: psum(hist) ordered-fold(proj) depth-resolve(slice)",
         repeats=MESH_REPS)
    return frac


# ------------------------------------------------ live lane-backend mode

LIVE_STEPS = 4
LIVE_REPS = 4
LIVE_RESOLUTION = 512


def _live_reducers():
    """Reduction-bound DAG for the lane-scaling measurement.

    Deliberately no LOD pass-through: its whole-tree write is
    GIL-released file I/O that thread lanes already parallelize (and
    the write trajectory is covered by insitu.multidomain_write_*).
    Rasterization at a viz-realistic resolution is where lanes spend
    GIL-held CPU (np.add.at, per-node paint loops, per-level
    histograms) — the work a process lane actually takes off the
    producer's interpreter.
    """
    from repro.insitu import (LevelHistogramReducer, ProjectionReducer,
                              SliceReducer)
    return [SliceReducer(field="density", axis=2, position=0.5,
                         resolution=LIVE_RESOLUTION),
            ProjectionReducer(field="density",
                              resolution=LIVE_RESOLUTION),
            LevelHistogramReducer(field="density", bins=64,
                                  lo=0.0, hi=50.0)]


def run_live_backends() -> float:
    """Live-pipeline lane scaling: the engine's thread vs process
    backends on identical pre-partitioned steps (block policy with a
    deep queue, nothing drops — both backends do exactly the same
    reduce+write work end to end, staging included). Thread lanes share
    the GIL; process lanes run over shared-memory staging. Returns the
    process/thread throughput ratio at ``max(GROUPS)`` contributor
    groups (the PR-4 acceptance bar: >1.3x on the CI runner)."""
    from repro.insitu.partition import partition_snapshot
    tree, _, _ = orion_domains(16)
    arrays = tree.to_arrays()
    parts = {n: partition_snapshot(arrays, "amr", n) for n in GROUPS}
    configs = [("thread", max(GROUPS))] + [("process", n) for n in GROUPS]
    times, sizes = {}, {}
    for backend, n in configs:
        root = scratch_dir(f"hx_bench_live_{backend}{n}_")
        eng = InTransitEngine(root, _live_reducers(), domains=n,
                              backend=backend, policy="block",
                              queue_capacity=4, ncf=1).start()
        eng.submit_parts(LIVE_STEPS + 1, parts[n])   # warm lanes/imports
        eng.drain(timeout=300.0)
        best, step = float("inf"), LIVE_STEPS + 1
        for _ in range(LIVE_REPS):
            t0 = time.perf_counter()
            for _ in range(LIVE_STEPS):
                step += 1
                eng.submit_parts(step, parts[n])
            eng.drain(timeout=300.0)
            best = min(best, time.perf_counter() - t0)
        eng.close()
        db = HerculeDB.open(root)
        ctxs = db.contexts()
        assert len(ctxs) == LIVE_REPS * LIVE_STEPS + 1, ctxs
        sizes[(backend, n)] = sum(r.nbytes for s in ctxs[-LIVE_STEPS:]
                                  for r in db.view(s).records)
        db.close()
        times[(backend, n)] = best
        shutil.rmtree(root, ignore_errors=True)
    thr = {k: sizes[k] / times[k] for k in times}
    for backend, n in configs:
        # step speedup = wall-time ratio at equal step count (bytes/ctx
        # grow with n — every domain rasters its own full-res part — so
        # MB/s does not compare across group counts, only backends)
        speedup = times[(backend, 1)] / times[(backend, n)] \
            if (backend, 1) in times else float("nan")
        emit(f"insitu.live_{backend}_g{n}",
             times[(backend, n)] / LIVE_STEPS * 1e6,
             f"{thr[(backend, n)]/1e6:.0f}MB/s live reduce+write "
             f"step_speedup={speedup:.2f}x lanes={n} "
             f"{sizes[(backend, n)]/LIVE_STEPS/1e6:.1f}MB/ctx policy=block",
             repeats=LIVE_REPS)
    g = max(GROUPS)
    ratio = thr[("process", g)] / thr[("thread", g)]
    emit(f"insitu.live_process_vs_thread_g{g}", ratio,
         f"process lanes over shm staging vs GIL-shared thread lanes "
         f"at {g} groups (acceptance floor 1.3x)", unit="ratio",
         repeats=LIVE_REPS)
    return ratio


# --------------------------------------------- observability overhead

OBS_STEPS = 5
OBS_REPS = 8


def run_obs_overhead() -> float:
    """Telemetry cost on the live pipeline: instrumented vs bare.

    One thread-backend engine runs identical step batches with the
    metrics kill switch (``repro.obs.metrics.ENABLED``) flipped each
    rep — interleaved best-of, so machine drift hits both arms alike.
    Tracing stays off in both arms (it is opt-in at runtime); what's
    measured is the always-on cost: histogram observes on the submit /
    reduce / write / commit paths plus the staging stat words. Emits
    ``insitu.obs_overhead_pct`` (CI ceiling: 2%).
    """
    from repro.obs import metrics as obs_metrics
    tree, _, _ = orion_domains(16)
    slicer = SliceReducer(field="density", axis=2, position=0.5,
                          resolution=RESOLUTION)
    root = scratch_dir("hx_bench_obs_")
    eng = InTransitEngine(root, [slicer], policy="block",
                          queue_capacity=4).start()
    step = 0
    for _ in range(OBS_STEPS):          # warm lanes, page caches
        step += 1
        eng.submit(step, tree)
    eng.drain(timeout=300.0)
    best = {False: float("inf"), True: float("inf")}
    try:
        for rep in range(OBS_REPS):
            # alternate which arm goes first: a drifting machine (cache
            # warmth, turbo decay) must not bias one arm systematically
            order = (False, True) if rep % 2 == 0 else (True, False)
            for enabled in order:
                obs_metrics.set_enabled(enabled)
                t0 = time.perf_counter()
                for _ in range(OBS_STEPS):
                    step += 1
                    eng.submit(step, tree)
                eng.drain(timeout=300.0)
                best[enabled] = min(best[enabled],
                                    time.perf_counter() - t0)
    finally:
        obs_metrics.set_enabled(True)
        eng.close()
    shutil.rmtree(root, ignore_errors=True)
    pct = max(0.0, 100.0 * (best[True] - best[False]) / best[False])
    emit("insitu.obs_overhead_pct", pct,
         f"instrumented {best[True]/OBS_STEPS*1e3:.2f}ms/step vs bare "
         f"{best[False]/OBS_STEPS*1e3:.2f}ms/step, thread backend, "
         f"best-of-{OBS_REPS} interleaved (ceiling 2%)",
         unit="pct", repeats=OBS_REPS)
    _run_ledger_arm(tree, slicer, best[True])
    return pct


def _run_ledger_arm(tree, slicer, instrumented_best: float) -> None:
    """Third arm: the run ledger on top of the instrumented pipeline.

    Tracing + events + a manual-cadence :class:`RunLedger` bound to the
    engine — the full flight-recorder stack, flushed once per step
    batch (a far hotter cadence than the default 2 s interval, so the
    measured cost is an upper bound). Emits the wall overhead vs the
    instrumented arm (informational) and ``obs.ledger_bytes_per_step``
    — the durable telemetry footprint per pipeline step, which gets a
    CI ceiling: a ledger that silently bloats its flushes would blow a
    run's storage budget long before it blows its time budget.
    """
    from repro.obs import RunLedger, TRACER
    root = scratch_dir("hx_bench_ledger_")
    ledger = RunLedger(root, "trainer", interval=0)
    eng = InTransitEngine(root, [slicer], policy="block",
                          queue_capacity=4, ledger=ledger).start()
    prev_traced = TRACER.enabled
    TRACER.enabled = True
    step = 0
    best = float("inf")
    try:
        for _ in range(OBS_STEPS):      # warm lanes, page caches
            step += 1
            eng.submit(step, tree)
        eng.drain(timeout=300.0)
        ledger.flush()
        steps_before = step
        bytes_before = ledger.bytes_written
        for _ in range(OBS_REPS):
            t0 = time.perf_counter()
            for _ in range(OBS_STEPS):
                step += 1
                eng.submit(step, tree)
            eng.drain(timeout=300.0)
            ledger.flush()
            best = min(best, time.perf_counter() - t0)
        bytes_per_step = (ledger.bytes_written - bytes_before) \
            / (step - steps_before)
    finally:
        TRACER.enabled = prev_traced
        eng.close()
        ledger.close()
    shutil.rmtree(root, ignore_errors=True)
    pct = max(0.0, 100.0 * (best - instrumented_best) / instrumented_best)
    emit("insitu.ledger_overhead_pct", pct,
         f"ledger+trace {best/OBS_STEPS*1e3:.2f}ms/step vs instrumented "
         f"{instrumented_best/OBS_STEPS*1e3:.2f}ms/step, one flush per "
         f"{OBS_STEPS}-step batch (informational)",
         unit="pct", repeats=OBS_REPS)
    emit("obs.ledger_bytes_per_step", bytes_per_step,
         f"durable telemetry footprint: spans+events+attribution+health "
         f"per pipeline step at per-batch flush cadence",
         unit="bytes", repeats=OBS_REPS)


# ------------------------------------------------------- serving mode

SERVE_VIEWERS = 64


def run_serve(n_viewers: int = SERVE_VIEWERS) -> float:
    """Concurrent-viewer serving: coalescing ratio, sustained QPS, p99.

    The paper's many-viewers scenario at benchmark scale. Arm one is
    the uncoalesced path the issue names — every request pays its own
    decode+merge (a no-cache catalog: the LRU only helps once an
    object is *warm*, and on a cold storm concurrent misses race past
    it nondeterministically). Arm two routes the same storm through
    ``ServeEngine.fetch`` over a cold cache: single-flight coalescing
    collapses the herd onto one backend read, deterministically. Their
    read-count ratio is ``insitu.serve_coalesce_ratio_c64`` (CI floor:
    5x; acceptance: ≥5x at 64 viewers).

    The HTTP leg then measures end-to-end serving through
    ``CatalogServer`` + ``RemoteCatalog`` — ``insitu.serve_qps``
    (sustained, warm cache: the dashboard steady state) and
    ``insitu.serve_p99_ms`` (per-request wall time incl. connection
    setup) at the same concurrency.
    """
    import threading

    from repro.insitu import CatalogServer, RemoteCatalog, ServeEngine

    tree, _, _ = orion_domains(4)
    root = scratch_dir("hx_bench_serve_")
    eng = InTransitEngine(root, _live_reducers(), domains=2,
                          policy="block", queue_capacity=4).start()
    eng.submit(1, tree)
    eng.drain(timeout=300.0)
    eng.close()

    def storm(call):
        bar = threading.Barrier(n_viewers)
        errs = []

        def go(i):
            bar.wait()
            try:
                call(i)
            except Exception as exc:        # noqa: BLE001 — surfaced below
                errs.append(exc)
        ts = [threading.Thread(target=go, args=(i,))
              for i in range(n_viewers)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise RuntimeError(f"serve storm errors: {errs[:3]}")
        return time.perf_counter() - t0

    cat = Catalog(root)
    name = cat.reducers(1)[0]

    # -- arm 1: per-request decode+merge -> n_viewers reads
    uncached = Catalog(root, cache_entries=0)
    t_direct = storm(lambda i: uncached.query(1, name))
    reads_direct = uncached.cache_info()["misses"]
    uncached.close()

    # -- arm 2: the same herd through the serving engine -> 1 read
    cat.clear_cache()
    serve = ServeEngine(cat, workers=4)
    t_engine = storm(lambda i: serve.fetch(1, name, client=f"v{i}"))
    st = serve.stats()
    serve.close()
    reads_engine = max(1, st["backend_reads"])
    ratio = reads_direct / reads_engine
    emit("insitu.serve_coalesce_ratio_c64", ratio,
         f"{reads_direct} direct reads vs {reads_engine} coalesced "
         f"({st['coalesced']} joined flights, {st['cache_serves']} "
         f"cache-served) at {n_viewers} viewers; "
         f"storm {t_direct*1e3:.0f}ms -> {t_engine*1e3:.0f}ms "
         f"(floor 5x)", unit="x")

    # -- HTTP leg: sustained QPS + p99 at the same concurrency
    srv = CatalogServer(cat, port=0).start()
    lat: list[float] = []
    lock = threading.Lock()
    per_viewer = 8
    regions = [None, ((0, 128), (0, 128)), ((64, 192), (64, 192))]
    RemoteCatalog(srv.url).query(1, name)   # warm the server cache

    def viewer(i):
        rc = RemoteCatalog(srv.url, client_id=f"v{i}")
        mine = []
        for q in range(per_viewer):
            t0 = time.perf_counter()
            rc.query(1, name, region=regions[(i + q) % len(regions)])
            mine.append(time.perf_counter() - t0)
        with lock:
            lat.extend(mine)

    elapsed = storm(viewer)
    srv.close()
    cat.close()
    shutil.rmtree(root, ignore_errors=True)
    qps = len(lat) / elapsed
    p99 = float(np.percentile(np.asarray(lat) * 1e3, 99))
    emit("insitu.serve_qps", qps,
         f"{len(lat)} requests over {n_viewers} viewers in "
         f"{elapsed:.2f}s, warm cache, region mix", unit="qps")
    emit("insitu.serve_p99_ms", p99,
         f"p50={np.percentile(np.asarray(lat)*1e3, 50):.1f}ms "
         f"mean={np.mean(lat)*1e3:.1f}ms", unit="ms")
    return ratio


# ------------------------------------------------- single-writer mode

def _compute_step(tree):
    """Stand-in compute work per step: touch the fields like a solver."""
    v = tree.fields["density"]
    return float(v.sum() + np.abs(v).max())


def run(n_domains: int = 16, steps: int = 8):
    tree, _, pruned = orion_domains(n_domains)
    slicer = SliceReducer(field="density", axis=2, position=0.5,
                          resolution=RESOLUTION)

    # -------- multi-domain contributor-group scaling + merge-at-read
    scaling = run_multidomain()

    # -------- live pipeline: thread vs process lane backends
    run_live_backends()

    # -------- device-resident staging + on-device reduction
    run_device()

    # -------- sharded multi-device reduction (subprocess: forced mesh)
    run_mesh()

    # -------- telemetry overhead: instrumented vs bare, same engine
    run_obs_overhead()

    # -------- concurrent-viewer serving: coalescing, QPS, p99
    run_serve()

    # ---------------- compute loop, engine OFF
    t0 = time.perf_counter()
    for _ in range(steps):
        _compute_step(tree)
    t_off = time.perf_counter() - t0

    # ---------------- compute loop, engine ON (drop-oldest, never blocks)
    red_root = scratch_dir("hx_bench_insitu_")
    eng = InTransitEngine(red_root, [slicer], policy="drop-oldest",
                          queue_capacity=2).start()
    t0 = time.perf_counter()
    for s in range(1, steps + 1):
        _compute_step(tree)
        eng.submit(s, tree)
    t_on = time.perf_counter() - t0
    eng.drain()
    stats = eng.staging.stats
    overhead = (t_on - t_off) / steps
    emit("insitu.compute_overhead", overhead * 1e6,
         f"loop_off={t_off*1e3:.1f}ms loop_on={t_on*1e3:.1f}ms "
         f"accepted={stats.accepted} evicted={stats.evicted} "
         f"staged={stats.bytes_staged/1e6:.1f}MB policy=drop-oldest")
    eng.close()

    # ---------------- post-hoc baseline: full HDep objects -> assemble -> slice
    full_root = scratch_dir("hx_bench_posthoc_")
    db = HerculeDB.create(full_root, kind="hdep", ncf=4)
    ctx = db.begin_context(0)
    for d, pt in enumerate(pruned):
        api.write_object(ctx, "amr_tree", d, pt)
    ctx.finalize()

    def posthoc_slice():
        g = analysis.load_global_tree(db, 0)
        return analysis.slice_image(g, "density", axis=2, position=0.5,
                                    resolution=RESOLUTION)
    ref, t_posthoc = timeit(posthoc_slice, reps=2)

    # ---------------- in-transit catalog: cold read, then cached
    cat = Catalog(red_root)
    step = cat.steps()[-1]
    _, t_cold = timeit(lambda: cat.query(step, slicer.name), reps=1)
    img = cat.query(step, slicer.name)["image"]
    _, t_warm = timeit(lambda: cat.query(step, slicer.name), reps=5)
    assert img.shape == ref.shape
    emit("insitu.query_cold", t_cold * 1e6,
         f"vs_posthoc={t_posthoc*1e6:.0f}us "
         f"speedup={t_posthoc/max(t_cold,1e-9):.1f}x")
    emit("insitu.query_cached", t_warm * 1e6,
         f"speedup_vs_posthoc={t_posthoc/max(t_warm,1e-9):.0f}x "
         f"cache={cat.cache_info()}")
    shutil.rmtree(red_root, ignore_errors=True)
    shutil.rmtree(full_root, ignore_errors=True)
    return scaling


if __name__ == "__main__":
    import sys as _sys
    if "--mesh-child" in _sys.argv:
        _mesh_child()
    else:
        run()
