"""HProt async sharded checkpoint manager with delta checkpoints.

The paper's protection flow, rebuilt on this repo's staging + lane
machinery (DESIGN.md §16). One save decomposes into four pipeline
stages, each its own span:

  ``ckpt.snapshot``  (train thread, *the only stall*) — a
      snapshot-consistent cut of the state, donation-safe: the optimizer
      may overwrite the source buffers the moment save returns. Owned
      shards are copied device-side (``jnp.array``, child span
      ``ckpt.cut.device``) while their bytes fit the HBM the steps leave
      free (``_cut_budget``); the rest cross to the host inside the
      stall, every transfer started before any is waited on (child span
      ``ckpt.cut.host``, counter ``ckpt_cut_host_bytes``). Where the
      device reports no memory figures (CPU) every shard is copied on
      the device. No serialization.
  ``ckpt.stage``     (gather thread) — device copies cross to the host
      *one at a time* (bounded host memory), are delta- or raw-encoded,
      CRC32-stamped and pushed into the owning contributor group's
      staging area; one child span each, in that order: ``ckpt.pull``,
      ``ckpt.encode``, ``ckpt.crc``, ``ckpt.enqueue`` (which blocks
      while the group's lane is behind). A raw shard is the pulled host
      array's own bytes, CRC'd in place: a thread lane's staging area
      holds it by reference (counter ``ckpt_zero_copy_bytes_total``), a
      process lane's copies it once into shared memory.
  ``ckpt.write``     (writer lanes, thread or process) — append to the
      group's Hercule files and publish to the page cache
      (``flush_domain(sync=False)``); no fsync here.
  ``ckpt.commit``    — once every shard of the *oldest* in-flight step
      has landed, the referenced files are fsynced and the manifest
      atomically replaced (``HerculeDB.commit_context``). Commits are
      strictly save-ordered so a delta context can never become
      readable before its predecessor.

A crash anywhere before the commit leaves no manifest: restart falls
back to the previous complete step (``restore.latest_complete_step``).
A writer-lane crash fails every in-flight step and surfaces on the
next ``save``/``wait`` — never a silent half-checkpoint, never a
deadlocked barrier.

Delta checkpoints (``delta_every=K``): checkpoint k in each cycle of
K+1 stores each float tensor as an ``fpdelta-delta`` residual against
the previous checkpoint (temporal father–son, the paper's time-chained
objects), with a periodic *full rebase* bounding every restore chain
at K links. Restore replays the chain bit-exactly through the
checksum-verifying decoder in :mod:`.restore`.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from ..core import pyramid as pyr
from ..hercule import api, codecs
from ..hercule.checkpoint import _FLOATY, _leaf_paths, _slices_json
from ..hercule.database import HerculeDB
from ..insitu.lanes import Lanes, check_mode
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs.trace import TRACER
from .lanes import HProtJob, ReferenceStagingArea
from .restore import latest_complete_step, verified_reader

_SENTINEL = object()


@dataclasses.dataclass
class _PendingSave:
    """One in-flight step between snapshot and manifest commit."""
    step: int
    attrs: dict
    tctx: dict | None                 # ckpt.snapshot span wire context
    expected: int | None = None       # shard count; None until gathered
    landed: int = 0
    records: list = dataclasses.field(default_factory=list)
    committing: bool = False          # commit claimed by some thread


def _host_copy(x: jax.Array) -> np.ndarray:
    """``x`` on the host, in memory that outlives ``x``'s buffers: an
    accelerator's transfer lands in a fresh host buffer, while the CPU
    backend may hand out a view of the device buffer itself."""
    host = np.asarray(x)
    return host.copy() if x.devices().pop().platform == "cpu" else host


class AsyncCheckpointManager:
    """Async sharded HProt checkpoints over staged writer lanes.

    Drop-in for :class:`~repro.hercule.checkpoint.CheckpointManager`
    (``save``/``wait``/``close``/``latest_step``/``restore``), but the
    train step only pays for the device-side snapshot; encoding, file
    I/O and durability all happen behind the staging areas.
    """

    def __init__(self, root: str, *, ncf: int = 8,
                 max_file_bytes: int = 2 << 30, delta_every: int = 0,
                 lane_backend: str = "thread", queue_capacity: int = 4,
                 io_threads: int = 4, registry=None,
                 cut_budget_bytes: int | None = None):
        """``cut_budget_bytes`` forces the snapshot's device-copy budget
        (tests only); by default it comes from the device's memory
        figures (``_cut_budget``)."""
        check_mode(lane_backend)      # before creating anything on disk
        self.db = HerculeDB.create(root, kind="hprot", ncf=ncf,
                                   max_file_bytes=max_file_bytes,
                                   io_threads=io_threads)
        self.delta_every = max(0, int(delta_every))
        # delta predictors: last checkpoint's host tensors (only kept
        # when delta encoding is on — they cost one state copy of RAM)
        self._prev: dict[tuple[str, int], np.ndarray] = {}
        self._prev_step: int | None = None
        self._deltas_since_full = 0

        self._lock = threading.Lock()
        self._done = threading.Condition(self._lock)
        self._pending: dict[int, _PendingSave] = {}
        self._order: list[int] = []          # steps in save order
        self._errors: list[BaseException] = []
        self._committed = 0
        self._stall_total = 0.0
        self._closed = False
        self._forced_budget = cut_budget_bytes
        # per device: the steps' own HBM peak, seen before any cut copy
        # existed, and the most cut bytes ever held on it at once
        self._step_peak: dict = {}
        self._cut_device_max: dict = {}

        self.obs = registry if registry is not None \
            else obs_metrics.MetricsRegistry()
        self._h_stall = self.obs.histogram(
            "ckpt_stall_seconds", "train-step stall per save (snapshot)")
        self._h_gather = self.obs.histogram(
            "ckpt_gather_seconds", "host gather+encode time per save")
        self._h_commit = self.obs.histogram(
            "ckpt_commit_seconds", "fsync + manifest commit time")
        self._h_write = self.obs.histogram(
            "ckpt_write_seconds", "lane write time per shard",
            labels=("group",))
        self._c_bytes = self.obs.counter(
            "ckpt_bytes_written_total", "encoded shard bytes staged",
            labels=("codec",))
        self._c_records = self.obs.counter(
            "ckpt_records_total", "checkpoint shard records staged")
        self._c_saves = self.obs.counter(
            "ckpt_saves_total", "checkpoints gathered", labels=("mode",))
        self._c_cut_host = self.obs.counter(
            "ckpt_cut_host_bytes", "snapshot bytes cut to the host in the "
            "stall (the device had no room for their copies)")
        self._cut_host_bytes = 0
        self._c_zero_copy = self.obs.counter(
            "ckpt_zero_copy_bytes_total", "raw shard bytes staged to a "
            "lane with no host copy")
        self._zero_copy_bytes = 0

        #: the writer lanes, one per contributor group, made on the
        #: first push to it (see insitu.lanes)
        self._backend = Lanes(
            HProtJob(self), lane_backend, root=self.db.root,
            queue_capacity=max(1, int(queue_capacity)), policy="block",
            area_cls=ReferenceStagingArea).start()
        # depth-1 hand-off: a save whose *predecessor* is still
        # gathering blocks — the paper's barrier on the previous flush
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._gather = threading.Thread(target=self._gather_main,
                                        name="hprot-gather", daemon=True)
        self._gather.start()

    # --------------------------------------------------------------- save
    def save(self, step: int, state, *, attrs: dict | None = None,
             wait: bool = False) -> None:
        """Cut a snapshot (the only synchronous part) and hand it off."""
        self.check_errors()
        step = int(step)
        t0 = time.perf_counter()
        with TRACER.span("ckpt.snapshot", cat="ckpt",
                         args={"step": step}) as sp:
            tctx = sp.context()
            cut = self._snapshot(state)
        pend = _PendingSave(step=step, attrs=dict(attrs or {}), tctx=tctx)
        with self._lock:
            if step in self._pending:
                raise ValueError(f"step {step} already in flight")
            self._pending[step] = pend
            self._order.append(step)
        self._q.put((step, cut))   # blocks while previous gather runs
        stall = time.perf_counter() - t0
        with self._lock:
            self._stall_total += stall
        if obs_metrics.ENABLED:
            self._h_stall.observe(stall)
        if wait:
            self.wait()

    #: HBM kept free beside the steps' peak and the cut's device copies
    CUT_MARGIN = 512 << 20

    def _cut_budget(self, device) -> int | None:
        """Bytes of device copies the cut may make on ``device``: its
        ``bytes_limit`` less the steps' peak less :attr:`CUT_MARGIN`;
        ``None`` (no limit) where the device reports no memory figures.

        The steps' peak is the peak of live buffers plus the peak of the
        memory programs reserve for their temporaries while they run (a
        TPU keeps the two apart: ``peak_bytes_in_use`` and
        ``peak_bytes_reserved``), read at the first save, after the job's
        steps ran. ``peak_bytes_in_use`` also counts earlier cuts' copies,
        so a later reading less the most copy bytes ever held on that
        device raises the peak, and the budget does not shrink save after
        save by the cut's own copies. Each device keeps its own figures."""
        if self._forced_budget is not None:
            return self._forced_budget
        stats = device.memory_stats() if hasattr(device, "memory_stats") \
            else None
        if not stats or "bytes_limit" not in stats:
            return None
        used = int(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)))
        reserved = int(stats.get("peak_bytes_reserved",
                                 stats.get("bytes_reserved", 0)))
        peak = used - self._cut_device_max.get(device, 0) + reserved
        peak = max(self._step_peak.get(device, peak), peak)
        self._step_peak[device] = peak
        return max(0, int(stats["bytes_limit"]) - peak - self.CUT_MARGIN)

    def _snapshot(self, state) -> list:
        """Donation-safe consistent cut: device copies within the budget,
        the rest pulled to the host before ``save`` returns."""
        cut, on_device, to_host = [], [], []
        budgets: dict = {}
        used: dict = {}
        for name, leaf in _leaf_paths(state):
            if leaf is None:
                continue
            if isinstance(leaf, jax.Array) and \
                    hasattr(leaf, "addressable_shards"):
                gshape = tuple(leaf.shape)
                seen = set()
                for sh in sorted(leaf.addressable_shards,
                                 key=lambda s: s.device.id):
                    key = tuple((s.start, s.stop, s.step) for s in sh.index)
                    if key in seen:
                        continue   # ghost replica — ownership pruning
                    seen.add(key)
                    dev = sh.device
                    if dev not in budgets:
                        budgets[dev] = self._cut_budget(dev)
                        used[dev] = 0
                    entry = [name, dev.id, _slices_json(sh.index, gshape),
                             gshape, sh.data]
                    nbytes = sh.data.nbytes
                    if budgets[dev] is None or \
                            used[dev] + nbytes <= budgets[dev]:
                        used[dev] += nbytes
                        on_device.append(entry)
                    else:
                        to_host.append(entry)
                    cut.append(entry)
            else:
                data = np.array(leaf, copy=True)
                cut.append([name, 0, [], tuple(data.shape), data])
        if on_device:
            with TRACER.span("ckpt.cut.device", cat="ckpt",
                             args={"tensors": len(on_device)}):
                for entry in on_device:
                    entry[4] = jnp.array(entry[4])  # guaranteed device copy
                jax.block_until_ready([e[4] for e in on_device])
        if to_host:
            nbytes = sum(e[4].nbytes for e in to_host)
            with TRACER.span("ckpt.cut.host", cat="ckpt",
                             args={"tensors": len(to_host),
                                   "bytes": nbytes}):
                for entry in to_host:       # start every transfer first
                    entry[4].copy_to_host_async()
                for entry in to_host:
                    entry[4] = _host_copy(entry[4])
            with self._lock:
                self._cut_host_bytes += nbytes
            if obs_metrics.ENABLED:
                self._c_cut_host.inc(nbytes)
        for dev, nbytes in used.items():
            self._cut_device_max[dev] = max(
                self._cut_device_max.get(dev, 0), nbytes)
        return cut

    # ------------------------------------------------------------- gather
    def _gather_main(self) -> None:
        while True:
            job = self._q.get()
            if job is _SENTINEL:
                self._q.task_done()
                return
            step, cut = job
            try:
                self._gather_one(step, cut)
            except BaseException as e:   # noqa: BLE001 — surfaced on wait
                self._save_failed(step, e)
            finally:
                self._q.task_done()

    def _gather_one(self, step: int, cut: list) -> None:
        with self._lock:
            pend = self._pending.get(step)
        if pend is None:       # step already failed (e.g. lane crash)
            return
        full = (self.delta_every == 0 or self._prev_step is None
                or self._deltas_since_full >= self.delta_every)
        keep_prev = self.delta_every > 0
        new_prev: dict | None = {} if keep_prev else None
        g0 = time.perf_counter()
        count = zero_copy = 0
        for entry in cut:
            name, domain, slices, gshape, data = entry
            domain = int(domain)
            with TRACER.span("ckpt.stage", cat="ckpt", parent=pend.tctx,
                             args={"step": step, "tensor": name}):
                if isinstance(data, np.ndarray):
                    host = data               # cut to the host in the stall
                else:
                    with TRACER.span("ckpt.pull", cat="ckpt"):
                        host = np.asarray(data)   # one tensor on the host
                entry[4] = None               # release the device copy now
                with TRACER.span("ckpt.encode", cat="ckpt"):
                    codec, payload, meta = self._encode(name, domain, host,
                                                        full=full)
                with TRACER.span("ckpt.crc", cat="ckpt"):
                    crc = zlib.crc32(payload) & 0xFFFFFFFF   # in place
                # blocks while the owning group's lane is behind
                with TRACER.span("ckpt.enqueue", cat="ckpt"):
                    desc = {
                        "rec_name": api.HPROT_SHARD.record_name(name),
                        "domain": domain, "dtype": str(host.dtype),
                        "shape": list(host.shape), "codec": codec,
                        "rec_meta": {**meta, "slices": slices,
                                     "global_shape": list(gshape),
                                     "crc32": int(crc)},
                        "_trace": pend.tctx,
                    }
                    area = self._backend.area(self.db.group_of(domain))
                    area.push(step, {"payload": payload}, kind="ckpt",
                              meta=desc)
            count += 1
            if codec == "raw" and self._backend.mode == "thread":
                zero_copy += payload.nbytes
            if obs_metrics.ENABLED:
                self._c_bytes.labels(codec).inc(payload.nbytes)
                self._c_records.inc()
            if keep_prev:
                new_prev[(name, domain)] = host
        mode = "full" if full else "delta"
        if full and self.delta_every > 0 and self._prev_step is not None:
            # a *scheduled* full over an existing delta chain = a rebase
            obs_events.EVENTS.emit(obs_events.CKPT_REBASE, step=step,
                                   chain_len=self._deltas_since_full)
        if keep_prev:
            self._prev = new_prev
            self._prev_step = step
            self._deltas_since_full = 0 if full else \
                self._deltas_since_full + 1
        if obs_metrics.ENABLED:
            self._h_gather.observe(time.perf_counter() - g0)
            self._c_saves.labels(mode).inc()
            self._c_zero_copy.inc(zero_copy)
        with self._lock:
            pend.attrs["mode"] = mode
            pend.expected = count
            self._zero_copy_bytes += zero_copy
        self._try_commit()

    def _encode(self, name: str, domain: int, data: np.ndarray, *,
                full: bool):
        """(codec, payload, meta) for one shard; delta when it pays.

        ``payload`` is flat ``uint8``. A raw one is a view of ``data``'s
        own bytes, no copy (``data`` is a private host array the gather
        never writes to); ``reshape(-1).view`` also takes the bfloat16
        and 0-d arrays that ``memoryview.cast`` refuses."""
        if not full:
            prev = self._prev.get((name, domain))
            if str(data.dtype) in _FLOATY and data.size >= 64 \
                    and prev is not None and prev.shape == data.shape \
                    and prev.dtype == data.dtype:
                dc = pyr.encode_delta(data, prev)
                payload = codecs.encode_delta(dc)
                if len(payload) < data.nbytes:
                    return ("fpdelta-delta", np.frombuffer(payload, np.uint8),
                            {"pred_step": self._prev_step, "pad": dc.pad})
        return "raw", np.ascontiguousarray(data).reshape(-1).view(np.uint8), {}

    # -------------------------------------------------- lane-side reports
    def _shard_landed(self, step: int, group: int, records,
                      write_seconds: float | None = None) -> None:
        """One shard durable-in-page-cache; called from lane threads."""
        if write_seconds is not None and obs_metrics.ENABLED:
            self._h_write.labels(group).observe(write_seconds)
        with self._lock:
            pend = self._pending.get(step)
            if pend is None:
                return    # step failed after this shard was staged
            pend.records.extend(records)
            pend.landed += 1
        self._try_commit()

    def _lane_failed(self, group: int, exc: BaseException) -> None:
        """A writer lane crashed: no in-flight step can ever complete."""
        with self._lock:
            self._errors.append(exc)
            self._pending.clear()     # their manifests must never commit
            self._order.clear()
            self._done.notify_all()

    def _save_failed(self, step: int, exc: BaseException) -> None:
        with self._lock:
            self._errors.append(exc)
            self._pending.pop(step, None)
            if step in self._order:
                self._order.remove(step)
            self._done.notify_all()

    # -------------------------------------------------------------- commit
    def _try_commit(self) -> None:
        """Commit the oldest step once all its shards landed.

        Strictly save-ordered (head of ``_order`` only): a delta
        context becomes readable only after its predecessor's manifest
        exists. The ``committing`` flag serializes racing lane threads;
        the fsync+rename runs outside the manager lock.
        """
        while True:
            with self._lock:
                if not self._order:
                    return
                step = self._order[0]
                pend = self._pending.get(step)
                if pend is None:          # defensive: orphaned order slot
                    self._order.pop(0)
                    continue
                if pend.committing or pend.expected is None \
                        or pend.landed < pend.expected:
                    return
                pend.committing = True
                records = list(pend.records)
                attrs = dict(pend.attrs)
                tctx = pend.tctx
            try:
                c0 = time.perf_counter()
                with TRACER.span("ckpt.commit", cat="ckpt", parent=tctx,
                                 args={"step": step,
                                       "n_records": len(records)}):
                    self.db.commit_context(step, records, attrs=attrs)
                if obs_metrics.ENABLED:
                    self._h_commit.observe(time.perf_counter() - c0)
                with self._lock:
                    self._pending.pop(step, None)
                    if step in self._order:
                        self._order.remove(step)
                    self._committed += 1
                    self._done.notify_all()
                obs_events.EVENTS.emit(
                    obs_events.CKPT_COMMIT, step=step,
                    mode=attrs.get("mode", "full"),
                    n_records=len(records))
            except BaseException as e:    # noqa: BLE001
                self._save_failed(step, e)
                return

    # ---------------------------------------------------------------- sync
    def wait(self, timeout: float | None = None) -> None:
        """Barrier: every accepted save is committed (or failed)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._order and not self._errors:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"checkpoint steps {list(self._order)} still in "
                        f"flight after {timeout}s")
                self._done.wait(timeout=0.25 if remaining is None
                                else min(0.25, remaining))
        self.check_errors()

    def check_errors(self) -> None:
        with self._lock:
            errs = list(self._errors)
        if errs:
            raise RuntimeError(
                f"async checkpoint failed ({len(errs)} error(s)); "
                f"first: {errs[0]}") from errs[0]

    def close(self) -> None:
        """Drain, stop lanes, close the database. Idempotent; does not
        raise on previously accumulated errors (use ``wait`` for that)."""
        if self._closed:
            return
        self._closed = True
        self._q.join()
        self._q.put(_SENTINEL)
        self._gather.join()
        try:
            self._backend.stop()
        except TimeoutError as e:
            with self._lock:
                self._errors.append(e)
            return   # a lane may still be writing: leave the db open
        self.db.close()

    # -------------------------------------------------------------- restore
    def latest_step(self) -> int | None:
        """Newest *complete* step (manifest + every payload + delta chain)."""
        return latest_complete_step(self.db)

    def restore(self, template, step: int | None = None):
        """Verified elastic restore into ``template``'s topology.

        Every payload read is checksum-verified and delta chains replay
        through :func:`.restore.decode_verified` — corruption raises
        :class:`.restore.CorruptShardError` instead of restoring wrong
        weights. Returns ``(state, attrs)``.
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError("no complete checkpoint context found")
        view = self.db.view(step)
        reader = verified_reader(self.db, step)
        kind = api.HPROT_SHARD

        def restore_leaf(path, leaf):
            if leaf is None:
                return None
            name = kind.record_name(jax.tree_util.keystr(path))
            recs = kind.shards(view, name)
            if not recs:
                raise KeyError(f"checkpoint {step} missing tensor {name!r}")
            gshape = tuple(recs[0].meta["global_shape"])

            def read_region(target_slices):
                return kind.read_region(view, name, target_slices,
                                        reader=reader)

            sharding = getattr(leaf, "sharding", None)
            if isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct)) \
                    and sharding is not None:
                def cb(idx):
                    tslices = [slice(0 if s.start is None else s.start,
                                     dim if s.stop is None else s.stop)
                               for s, dim in zip(idx, gshape)]
                    return read_region(tslices)
                return jax.make_array_from_callback(gshape, sharding, cb)
            full = read_region([slice(0, d) for d in gshape]) if gshape \
                else read_region(())
            return jnp.asarray(full) if isinstance(leaf, jax.Array) \
                else full

        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = [restore_leaf(p, leaf) for p, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves), view.attrs

    # ------------------------------------------------------------ telemetry
    def bind_ledger(self, ledger) -> None:
        """Register this manager with a run ledger: its metrics become
        a flush source and ``ckpt_stall_ratio`` — the fraction of wall
        time the train thread spent stalled in ``save()`` since the
        previous ledger sample — feeds the health rules."""
        ledger.add_source("ckpt", self.obs.snapshot)
        sample = {"t": time.monotonic(), "stall": 0.0}

        def stall_ratio():
            now = time.monotonic()
            total = self.stall_seconds_total
            dt, dstall = now - sample["t"], total - sample["stall"]
            sample["t"], sample["stall"] = now, total
            if dt <= 0:
                return None
            return min(1.0, max(0.0, dstall / dt))

        ledger.add_signal("ckpt_stall_ratio", stall_ratio)

    @property
    def stall_seconds_total(self) -> float:
        """Cumulative train-thread time spent inside ``save()``."""
        with self._lock:
            return self._stall_total

    def telemetry(self) -> dict:
        with self._lock:
            return {"committed": self._committed,
                    "pending": len(self._order),
                    "errors": len(self._errors),
                    "stall_seconds_total": self._stall_total,
                    "cut_host_bytes": self._cut_host_bytes,
                    "zero_copy_bytes": self._zero_copy_bytes,
                    "delta_every": self.delta_every,
                    "deltas_since_full": self._deltas_since_full,
                    "backend": self._backend.telemetry()}
