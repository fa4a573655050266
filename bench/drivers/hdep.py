"""HDep in-transit reduction: an AMR producer feeding the device reducers.

Set-up generates one turbulent-cloud snapshot (``bench/ref/orion.py``;
its tree from the configuration's ``structure_seed``, so that every seed
runs the programs of the same shapes), derives the mix's field variants
from it with the seed (same structure, so one warm snapshot compiles
every program the window runs),
starts ``InTransitEngine(device_reduce=True)`` with the mix's reducer DAG
and pushes the warm snapshots through. The window is a closed-loop
producer: it submits the next variant as soon as ``submit`` returns, and
ends at the manifest commit of the last snapshot submitted before the
deadline, so the drain is inside the timing.

The check reads every object the window committed back through
``Catalog.query`` and compares it with the float64 host reducers of
``bench/ref/reducers.py`` on the same snapshot: relative error within
the mix's limit for each reducer, exact for histograms (against the
reference over the float32-rounded fields) and for every integer,
boolean and NaN position. A snapshot that was reduced on the host
(``fallback_snapshots``) or an object that is missing fails the run.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from ref import orion, reducers as ref_reducers  # noqa: E402
import work  # noqa: E402


def program_reducer(spec: dict, by_id: dict):
    """The program's reducer object for one traffic spec."""
    from repro.insitu.reducers import (LevelHistogramReducer, LODCutReducer,
                                       ProjectionReducer, SliceReducer)
    source = by_id[spec["source"]].name if spec.get("source") else None
    kind = spec["kind"]
    if kind == "slice":
        return SliceReducer(field=spec["field"], axis=spec["axis"],
                            position=spec["position"],
                            resolution=spec["resolution"], source=source)
    if kind == "projection":
        return ProjectionReducer(field=spec["field"], axis=spec["axis"],
                                 resolution=spec["resolution"], source=source)
    if kind == "hist":
        return LevelHistogramReducer(field=spec["field"], bins=spec["bins"])
    if kind == "lod":
        return LODCutReducer(max_level=spec["max_level"])
    raise ValueError(f"unknown reducer kind {kind!r}")


class Cell:
    ANNOTATIONS = ("submit", "drain")

    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 scratch: str, devices):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.db = os.path.join(scratch, "hdep")
        self.specs = traffic["reducers"]
        self.engine = None
        self.closed = False
        self.window_steps: list[int] = []
        self.step = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.insitu import InTransitEngine
        from repro.insitu.device import device_impl_for
        t0 = time.perf_counter()
        gen = self.config["generator"]
        base = orion.generate(gen, jitter_seed=gen["structure_seed"])
        t1 = time.perf_counter()
        self.snaps = orion.variants(base, self.traffic["variants"],
                                    self.traffic["perturbation"], self.seed)
        t2 = time.perf_counter()
        self.n_leaves = int((~base["refine"]).sum())
        self.n_nodes = int(base["refine"].shape[0])
        by_id, self.reducers = {}, []
        for s in self.specs:
            r = program_reducer(s, by_id)
            self.reducers.append(r)
            if s.get("id"):
                by_id[s["id"]] = r
        self.n_device = sum(device_impl_for(r) is not None
                            for r in self.reducers)
        self.work = {s["kind"]: work.raster_work(s["kind"], base, s)
                     for s in self.specs
                     if s.get("kernel") and not s.get("source")}
        self.engine = InTransitEngine(
            self.db, self.reducers, device_reduce=True, policy="block",
            queue_capacity=self.traffic["queue_capacity"])
        self.engine.start()
        for _ in range(self.traffic["warm_snapshots"]):
            self._submit()
        self.engine.drain(timeout=600.0)
        self.warm_steps = self.step
        print(f"[setup] generate_s={t1 - t0!r} variants_s={t2 - t1!r} "
              f"warm_s={time.perf_counter() - t2!r} nodes={self.n_nodes} "
              f"leaves={self.n_leaves}", file=sys.stderr, flush=True)

    def _submit(self) -> int:
        self.step += 1
        snap = self.snaps[self.step % len(self.snaps)]
        if not self.engine.submit(self.step, snap):
            raise RuntimeError(f"snapshot {self.step} was not staged")
        return self.step

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        import jax
        t0 = time.perf_counter()
        deadline = t0 + seconds
        returns = [t0]
        while returns[-1] < deadline:
            with jax.profiler.TraceAnnotation("submit"):
                self.window_steps.append(self._submit())
            returns.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("drain"):
            self.engine.drain(timeout=600.0)
        wall = time.perf_counter() - t0
        # a stall shows as one long gap between submits, a slow host as
        # a higher median
        gaps = np.diff(returns)
        print(f"[window] snapshots={gaps.size} submit_gap_median_s="
              f"{float(np.median(gaps))!r} submit_gap_max_s="
              f"{float(gaps.max())!r} "
              f"drain_s={t0 + wall - returns[-1]!r}", file=sys.stderr,
              flush=True)
        return {self.traffic["metric"]:
                len(self.window_steps) * self.n_leaves / wall}

    def counts(self) -> dict:
        return {"snapshots": len(self.window_steps), "leaves": self.n_leaves,
                "kernel_work": self.work}

    # ------------------------------------------------------------- check
    def verify(self):
        from repro.insitu import Catalog
        self.closed = True
        self.engine.close()
        stats = self.engine.device_stats
        written = set(self.engine.written_steps)
        n_snap = self.warm_steps + len(self.window_steps)
        checks_fb = [("fallback_snapshots", stats["fallback_snapshots"], 0),
                     ("device_objects_missing",
                      self.n_device * n_snap - stats["device_objects"], 0)]
        refs = {}
        for i, snap in enumerate(self.snaps):
            if any(step % len(self.snaps) == i for step in self.window_steps):
                full = ref_reducers.run_dag(self.specs, snap)
                cast = ref_reducers.run_dag(
                    self.specs, ref_reducers.round_fields(snap, np.float32))
                refs[i] = [c if s["kind"] == "hist" else f
                           for s, f, c in zip(self.specs, full, cast)]
        worst = {s["check"]: 0.0 for s in self.specs}
        mismatches = missing = attempted = failed = 0
        cat = Catalog(self.db)
        try:
            for step in self.window_steps:
                refs_i = refs[step % len(self.snaps)]
                have = set(cat.reducers(step)) if step in written else set()
                for s, r, want in zip(self.specs, self.reducers, refs_i):
                    attempted += 1
                    if r.name not in have:
                        missing += 1
                        failed += 1
                        continue
                    err, bad = ref_reducers.compare(cat.query(step, r.name),
                                                    want)
                    worst[s["check"]] = max(worst[s["check"]], err)
                    mismatches += bad
                    failed += int(bad > 0 or err > s["limit"])
        finally:
            cat.close()
        limits = {s["check"]: s["limit"] for s in self.specs}
        checks = [(name, worst[name], limits[name]) for name in worst]
        checks += [("exact_mismatches", mismatches, 0),
                   ("objects_missing", missing, 0)] + checks_fb
        return checks, attempted, failed

    def close(self) -> None:
        if self.engine is not None and not self.closed:
            self.closed = True
            self.engine.close(drain=False)
