#!/usr/bin/env python3
"""Drive the system's main path once on a TPU chip and check the results.

    python chip_smoke.py              # one chip: reduce, train + HProt, serve
    python chip_smoke.py --chips 4    # four chips: the sharded mesh reduce

One chip runs the paper's three flows through their normal entry points:

1. reduce — a turbulent-cloud AMR producer (``sim.fields.orion``, more
   than 1M leaves per snapshot) submits snapshots to
   ``InTransitEngine(device_reduce=True)``; the default CLI DAG at R=512
   reduces them on the Pallas kernels, the objects land in the Hercule
   DB and are read back through ``Catalog.query`` and checked against
   the float64 host reducers (DESIGN.md §18 tolerances).
2. train — a ``Trainer`` takes full-width ``stablelm_1_6b`` steps (depth
   cut to fit one chip) with ``AsyncCheckpointManager``, saves, and a
   fresh trainer restores the checkpoint bit for bit.
3. serve — a ``CatalogServer`` answers ``RemoteCatalog`` queries on the
   phase-1 DB, byte-equal to the local ``Catalog``.

``--chips 4`` runs only the sharded path: the phase-1 snapshot goes
through ``InTransitEngine(device_reduce="mesh")`` over a 4-device and
a 1-device mesh, and both catalogs are checked against the host
reducers and against each other.

Everything runs in this process (every lane is a thread): the chip
belongs to one process. Data comes from ``--seed``; scratch files go to
``<checkout>/.chip_smoke`` and are removed at exit. Every line but the
last is a report; the last is one JSON object naming the device. The
script exits non-zero, with no such line, when JAX finds no TPU, or when
any phase fails or falls back to the host.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")

#: the paper's turbulent cloud: one realization (the bench's seed 7) at
#: refinement level 10 holds ~1.2M leaves; ``--seed`` drives per-step
#: refinement jitter, so every step is a different tree of that size
CLOUD_SEED = 7
MAX_LEVEL = 10
MIN_LEAVES = 1_000_000
JITTER = 0.05
RESOLUTION = 512
LOD = 4
REDUCE_STEPS = 3

#: stablelm-1.6b at its published widths, 4 of its 24 layers: params,
#: Adam moments and the checkpoint's device-side snapshot fit 16 GB
TRAIN_ARCH = "stablelm_1_6b"
TRAIN_LAYERS = 4
TRAIN_STEPS = 3
TRAIN_SEQ = 512
TRAIN_BATCH = 4

SERVE_MIN_QUERIES = 8
MESH_MAX_LEAF_FRAC = 0.3


class SmokeFailure(RuntimeError):
    """A phase failed, was skipped or fell back to the host."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileMeter:
    """Seconds JAX spends compiling (or loading from the persistent
    cache), and the cache's hit and miss counts."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses

    def since(self, mark) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 3),
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}


def require_tpu(chips: int):
    """The TPU devices JAX sees; a missing chip is a failure."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU chip: JAX found no backend ({e})")
    platform = devices[0].platform
    check(platform == "tpu",
          f"no TPU chip: JAX runs on {platform!r} "
          f"({len(devices)} device(s)); this smoke needs a TPU")
    check(len(devices) >= chips,
          f"{chips} TPU chips requested, JAX sees {len(devices)}")
    return devices[:chips]


# ------------------------------------------------------------- reduce

def snapshot_tree(seed: int, step: int):
    from repro.sim import amrgen, fields
    return amrgen.generate_tree(
        fields.orion(seed=CLOUD_SEED), min_level=3, max_level=MAX_LEVEL,
        threshold=1.0, level_factor=1.6, rng_jitter=JITTER,
        seed=seed * 1000 + step)


def host_references(reducers, arrays: dict):
    """float64 host outputs, and the histograms over the cast values."""
    from repro.insitu.device import cast_like_device, parity_rtol
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot
    ref = ReducerDAG(reducers).run(
        Snapshot(step=0, kind="amr", arrays=arrays))
    exact = [r for r in reducers if parity_rtol(r) is None]
    ref_cast = ReducerDAG(exact).run(
        Snapshot(step=0, kind="amr", arrays=cast_like_device(arrays)))
    return ref, ref_cast


def check_catalog(db: str, refs: dict, reducers, worst: dict) -> None:
    """Every object of ``db`` within its §18 limit of the host reducers.

    ``refs`` maps each step to its :func:`host_references`; ``worst``
    collects ``reducer name -> (max error, limit)``.
    """
    from repro.insitu import Catalog
    from repro.insitu.device import parity_check
    cat = Catalog(db)
    try:
        check(cat.steps() == sorted(refs), f"catalog steps {cat.steps()}")
        for step, (ref, ref_cast) in refs.items():
            for r in reducers:
                err, limit = parity_check(r, cat.query(step, r.name),
                                          ref[r.name], ref_cast.get(r.name))
                check(err <= limit, f"{db} step {step} {r.name}: error "
                                    f"{err!r} > limit {limit!r}")
                worst[r.name] = (max(worst.get(r.name, (0.0,))[0], err),
                                 limit)
    finally:
        cat.close()


def phase_reduce(db: str, seed: int, meter: CompileMeter) -> list[int]:
    from repro.insitu import InTransitEngine
    from repro.insitu.device import device_impl_for
    from repro.kernels import ops
    from repro.launch.insitu import default_reducers
    from repro.obs import TRACER

    backend = ops.default_backend()
    check(backend == "pallas", f"kernel backend {backend!r}, not pallas")
    reducers = default_reducers(RESOLUTION, LOD)
    on_device = [r.name for r in reducers if device_impl_for(r)]
    report("reduce", backend=backend, resolution=RESOLUTION,
           reducers=",".join(r.name for r in reducers),
           device_reducers=len(on_device))

    TRACER.enable()
    TRACER.clear()
    engine = InTransitEngine(db, reducers, device_reduce=True,
                             policy="block", queue_capacity=REDUCE_STEPS)
    engine.start()
    trees, leaves = {}, {}
    try:
        for step in range(1, REDUCE_STEPS + 1):
            t0 = time.perf_counter()
            tree = snapshot_tree(seed, step)
            t1 = time.perf_counter()
            mark = meter.mark()
            check(engine.submit(step, tree), f"step {step} not staged")
            t2 = time.perf_counter()
            engine.drain()
            t3 = time.perf_counter()
            arrays = tree.to_arrays()
            trees[step] = arrays
            leaves[step] = int((~arrays["refine"]).sum())
            report("reduce", step=step, leaves=leaves[step],
                   nodes=tree.n_nodes, gen_s=round(t1 - t0, 3),
                   submit_s=round(t2 - t1, 4),
                   reduce_s=round(t3 - t2, 3), **meter.since(mark))
    finally:
        engine.close()
    TRACER.disable()
    check(min(leaves.values()) >= MIN_LEAVES,
          f"snapshots of {min(leaves.values())} leaves < {MIN_LEAVES}")

    ds = engine.device_stats
    staged = sum(a.stats.bytes_staged for a in engine.stages)
    report("reduce", snapshots=ds["snapshots"],
           device_objects=ds["device_objects"],
           fallback_snapshots=ds["fallback_snapshots"],
           fallback_runs=json.dumps(ds["fallback_runs"], sort_keys=True),
           bytes_staged=staged, bytes_to_host=ds["bytes_to_host"],
           bytes_reduced_to_host=ds["bytes_reduced_to_host"])
    check(ds["fallback_snapshots"] == 0,
          f"fallback_snapshots={ds['fallback_snapshots']}")
    check(ds["device_objects"] == len(on_device) * REDUCE_STEPS,
          f"device_objects={ds['device_objects']}, expected "
          f"{len(on_device)} x {REDUCE_STEPS}")

    # per-reducer device time: the "device.transfer" span wraps the
    # impl call and the host pull, where the device work lands
    per = {}
    for sp in TRACER.spans():
        if sp["name"] == "device.transfer":
            per.setdefault(sp["args"]["reducer"], []).append(
                sp["dur"] / 1e6)
    for name in on_device:
        times = per.get(name, [])
        report("reduce", reducer=name,
               seconds=",".join(f"{t:.4f}" for t in times))

    worst = {}
    check_catalog(db, {step: host_references(reducers, arrays)
                       for step, arrays in trees.items()}, reducers, worst)
    for name, (err, limit) in worst.items():
        report("reduce", reducer=name, max_error=repr(err),
               limit=repr(limit))
    return sorted(trees)


# -------------------------------------------------------------- train

def _bitwise_equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def phase_train(ckpt_dir: str, seed: int, meter: CompileMeter) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.models.transformer import LM
    from repro.train.trainer import Trainer

    full = get_config(TRAIN_ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    report("train", arch=TRAIN_ARCH, d_model=cfg.d_model,
           n_heads=cfg.n_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
           depth_cut=f"{cfg.n_layers}/{full.n_layers}",
           params=cfg.param_count(), seq_len=TRAIN_SEQ, batch=TRAIN_BATCH)
    lm = LM(cfg)

    def trainer():
        return Trainer(lm, data_cfg=DataConfig(
            vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
            global_batch=TRAIN_BATCH, seed=seed),
            ckpt_dir=ckpt_dir, ckpt_every=TRAIN_STEPS + 1, ckpt_async=True,
            log_every=1, seed=seed)

    mark = meter.mark()
    t0 = time.perf_counter()
    tr = trainer()
    state = tr.run(TRAIN_STEPS)     # the last step saves, then waits
    wall = time.perf_counter() - t0
    losses = [m["loss"] for m in tr.metrics_log]
    check(len(losses) == TRAIN_STEPS,
          f"{len(losses)} steps of {TRAIN_STEPS}")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    step_s = [m["dt"] for m in tr.metrics_log]
    ck = tr.ckpt.telemetry()
    check(ck["committed"] == 1 and ck["errors"] == 0,
          f"checkpoint telemetry {ck}")
    report("train", losses=",".join(f"{x:.4f}" for x in losses),
           step_s=",".join(f"{x:.3f}" for x in step_s),
           save_stall_s=round(ck["stall_seconds_total"], 4),
           init_save_commit_s=round(wall - sum(step_s), 3),
           ckpt_bytes=_dir_bytes(ckpt_dir), **meter.since(mark))
    mem = jax.devices()[0].memory_stats() or {}
    report("train", peak_bytes_in_use=mem.get("peak_bytes_in_use"),
           bytes_limit=mem.get("bytes_limit"))

    saved = [np.asarray(x) for x in jax.tree.leaves(state)]
    for x in jax.tree.leaves(state):
        x.delete()
    del state
    t0 = time.perf_counter()
    fresh = trainer()
    restored, at = fresh.init_or_restore()
    leaves = jax.tree.leaves(restored)
    jax.block_until_ready(leaves)
    restore_s = time.perf_counter() - t0
    fresh.ckpt.close()
    check(at == TRAIN_STEPS, f"restored step {at}, saved {TRAIN_STEPS}")
    check(len(leaves) == len(saved), "restored tree differs")
    bad = sum(not _bitwise_equal(a, b) for a, b in zip(leaves, saved))
    check(bad == 0, f"{bad} of {len(saved)} restored tensors differ")
    report("train", restored_step=at, tensors=len(saved),
           restored_bitwise=True, restore_s=round(restore_s, 3))


def _dir_bytes(root: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(root) for f in fs)


# -------------------------------------------------------------- serve

def phase_serve(db: str, steps: list[int]) -> None:
    from repro.insitu import Catalog, CatalogServer, RemoteCatalog

    cat = Catalog(db)
    srv = CatalogServer(cat, port=0).start()
    try:
        rc = RemoteCatalog(srv.url)
        n = 0
        lat = []
        for step in steps:
            for name in cat.reducers(step):
                t0 = time.perf_counter()
                remote = rc.query(step, name)
                lat.append(time.perf_counter() - t0)
                local = cat.query(step, name)
                check(set(remote) == set(local), f"{step}/{name} keys")
                for k, v in local.items():
                    check(_bitwise_equal(remote[k], v),
                          f"{step}/{name}/{k} differs from local")
                n += 1
        check(n >= SERVE_MIN_QUERIES, f"{n} queries < {SERVE_MIN_QUERIES}")
        report("serve", url=srv.url, queries=n, byte_equal=True,
               mean_ms=round(1e3 * sum(lat) / n, 3),
               max_ms=round(1e3 * max(lat), 3))
    finally:
        srv.close()
        cat.close()


# --------------------------------------------------------------- mesh

def phase_mesh(root: str, seed: int, n_devices: int,
               meter: CompileMeter) -> None:
    from repro.insitu import Catalog, InTransitEngine
    from repro.insitu.device import parity_error, parity_rtol
    from repro.insitu.mesh_reduce import MeshTable
    from repro.kernels import ops
    from repro.launch.insitu import default_reducers

    backend = ops.default_backend()
    check(backend == "pallas", f"kernel backend {backend!r}, not pallas")
    arrays = snapshot_tree(seed, 1).to_arrays()
    n_leaves = int((~arrays["refine"]).sum())
    check(n_leaves >= MIN_LEAVES, f"{n_leaves} leaves < {MIN_LEAVES}")
    reducers = default_reducers(RESOLUTION, LOD)
    # the phase-1 snapshot, submitted twice: the first run compiles
    ref = host_references(reducers, arrays)
    refs = {1: ref, 2: ref}
    dbs, engines = {}, {}
    for s in (1, n_devices):
        dbs[s] = os.path.join(root, f"mesh{s}")
        engine = engines[s] = InTransitEngine(
            dbs[s], reducers, device_reduce="mesh", mesh_devices=s,
            policy="block", queue_capacity=len(refs))
        engine.start()
        secs = []
        mark = meter.mark()
        try:
            for step in refs:
                t0 = time.perf_counter()
                check(engine.submit(step, arrays), f"S={s} step {step}")
                engine.drain()
                secs.append(time.perf_counter() - t0)
        finally:
            engine.close()
        st = engine.device_stats
        report("mesh", devices=s, backend=backend, leaves=n_leaves,
               first_s=round(secs[0], 3), second_s=round(secs[1], 3),
               peak_leaf_frac=round(st["peak_leaf_frac"], 4),
               peak_device_table_bytes=st["peak_device_table_bytes"],
               fallback_snapshots=st["fallback_snapshots"],
               **meter.since(mark))
        check(st["fallback_snapshots"] == 0, f"S={s} host fallback")
        worst = {}
        check_catalog(dbs[s], refs, reducers, worst)
        for name, (err, limit) in worst.items():
            report("mesh", devices=s, reducer=name, max_error=repr(err),
                   limit=repr(limit))
    check(st["peak_leaf_frac"] <= MESH_MAX_LEAF_FRAC,
          f"peak_leaf_frac {st['peak_leaf_frac']} > {MESH_MAX_LEAF_FRAC}")
    one, many = Catalog(dbs[1]), Catalog(dbs[n_devices])
    try:
        for r in reducers:
            limit = parity_rtol(r) or 0.0
            err = max(parity_error(many.query(step, r.name),
                                   one.query(step, r.name))
                      for step in refs)
            report("mesh", compare=f"S={n_devices}_vs_S=1", reducer=r.name,
                   max_error=repr(err), limit=repr(limit))
            check(err <= limit, f"S={n_devices} vs S=1 {r.name}: {err!r}")
    finally:
        one.close()
        many.close()
    # the engine's own mesh: one leaf-table shard on each device
    table = MeshTable(arrays, 1, engines[n_devices]._device.mesh)
    shards = table.coords.addressable_shards
    devices = sorted(sh.device.id for sh in shards)
    check(len(set(devices)) == n_devices
          and all(sh.data.shape[0] == 1 for sh in shards),
          f"leaf-table shards on devices {devices}")
    report("mesh", shard_devices=",".join(map(str, devices)),
           rows_per_device=table.rows_padded)


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 runs only the sharded mesh reduce")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.runtime import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = setup_compile_cache()
    meter = CompileMeter()
    t_start = time.perf_counter()
    try:
        devices = require_tpu(args.chips)
        dev = devices[0]
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        report("device", platform=dev.platform, kind=repr(dev.device_kind),
               count=len(devices), compile_cache=cache,
               disk_free_gb=round(shutil.disk_usage(WORK).free / 1e9, 1))
        phases = {}
        if args.chips == 1:
            t0 = time.perf_counter()
            steps = phase_reduce(os.path.join(WORK, "hdep"), args.seed,
                                 meter)
            phases["reduce"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            phase_train(os.path.join(WORK, "hprot"), args.seed, meter)
            phases["train"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            phase_serve(os.path.join(WORK, "hdep"), steps)
            phases["serve"] = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            phase_mesh(WORK, args.seed, args.chips, meter)
            phases["mesh"] = time.perf_counter() - t0
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    report("total", wall_s=round(time.perf_counter() - t_start, 3),
           **{f"{k}_s": round(v, 3) for k, v in phases.items()},
           compile_s=round(meter.seconds, 3), cache_hits=meter.hits,
           cache_misses=meter.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
