"""The lane runtime: how contributor-group lanes run, for both jobs.

A lane drains one contributor group's staging area. Two jobs use lanes:
the in-transit engine (``insitu/engine.py``, HDep: reduce a staged part
and write it as the group's Hercule domain) and the async checkpoint
manager (``ckpt/manager.py``, HProt: append a staged shard to the
group's files). Each hands the runtime a :class:`LaneJob`, which says
what a lane does with one staged snapshot and how the parent settles
the result; cadence, countdowns and manifest commits stay with the
caller. The runtime owns *how* a lane runs, in one of two modes:

  * ``thread``  — ``workers`` daemon threads per group over an
    in-process staging area of the caller's class; each runs the job's
    work in the caller's process.
  * ``process`` — one spawned OS process per group fed through a
    :class:`~repro.insitu.staging.ShmStagingArea` (shared-memory slabs,
    pickle-free descriptor headers), so the lane's work runs outside
    the producer's GIL. Each lane opens the database, runs the job's
    lane-side work and sends one :class:`LaneMsg` home per snapshot; a
    collector thread settles them, ingests the lanes' spans, relays
    their flight-recorder events and polls their exit codes.

Crash semantics: a lane process that dies with a nonzero exit code is
reported to its job (:meth:`LaneJob.failed`) and its staging area is
closed, so a producer blocked on it raises instead of deadlocking. Its
un-reported bytes stay orphaned in its own group files: no manifest
references them.
"""
from __future__ import annotations

import multiprocessing
import queue
import threading
import traceback
from typing import NamedTuple

from ..hercule.database import HerculeDB
from ..obs import events as obs_events
from ..obs.trace import TRACER, Tracer, now_us
from ..runtime import pin_to_cpu
from .staging import ShmStagingArea, StagingArea

MODES = ("thread", "process")


def check_mode(mode: str) -> str:
    """``mode`` if it names a lane mode; ValueError otherwise."""
    if mode not in MODES:
        raise ValueError(f"unknown lane backend {mode!r}; "
                         f"use one of {list(MODES)}")
    return mode


class LaneMsg(NamedTuple):
    """What a process lane sends home: one per snapshot, one at exit."""
    tag: str                       # "done", "skipped", "error" or "exit"
    step: int | None
    group: int
    records: list | None = None    # the Records the lane appended
    spans: list | None = None      # lane spans, linked to the push's trace
    timings: tuple | None = None   # job-defined wall seconds
    events: list | None = None     # the lane's flight-recorder drain
    extras: object = None          # job data; the traceback on "error"


class LaneJob:
    """What one caller's lanes do. The runtime calls these hooks.

    Thread mode calls :meth:`work` on a lane thread with each popped
    snapshot. Process mode runs ``lane_work(db, group, snap, tracer,
    t_pop, *lane_args)`` in the lane process — a module-level function,
    pickled by name, that records its spans on ``tracer`` and returns a
    :class:`LaneMsg` — and hands that message to :meth:`settle` on the
    collector thread. :meth:`idle` runs after each snapshot and on each
    idle poll. ``errors`` takes the :class:`TimeoutError` of lane
    processes :meth:`Lanes.stop` had to terminate; relayed lane events
    go to ``ledger`` when it is set.
    """

    #: names the lane threads, processes and the failures reported
    label = "lane"
    lane_work = None
    lane_args: tuple = ()
    ledger = None
    errors: list

    def work(self, group: int, snap, t_pop: float) -> None:
        """Thread mode: handle one snapshot and settle its failures.
        ``t_pop`` is when its pop began (0.0 while the tracer is off)."""
        raise NotImplementedError

    def idle(self) -> None:
        """Upkeep between snapshots (lane threads or the collector)."""

    def settle(self, msg: LaneMsg) -> None:
        """Process mode: a lane's "done" or "skipped" message."""
        raise NotImplementedError

    def failed(self, group: int, exc: BaseException, *,
               step: int | None = None,
               exitcode: int | None = None) -> None:
        """A lane's work failed at ``step`` (-1: its transport failed),
        or the lane process died with ``exitcode``."""
        raise NotImplementedError


def _lane_main(work, args: tuple, handle, root: str, group: int,
               results) -> None:
    """One lane process: attach the group's shm staging, run ``work``
    on each popped snapshot, send each result home."""
    pin_to_cpu()                     # the parent may hold the chip
    area = ShmStagingArea.attach(handle)
    db = HerculeDB.open(root)
    tracer = Tracer(enabled=True)    # only used when _trace rides in
    mark = 0                         # cursor into this process's events

    def send(msg: LaneMsg) -> None:
        # each message carries the spans and events since the last one
        nonlocal mark
        mark, evs = obs_events.EVENTS.drain_since(mark)
        spans = tracer.spans()
        tracer.clear()
        results.put(msg._replace(spans=spans or None, events=evs or None))

    try:
        while True:
            t_pop = now_us()
            try:
                snap = area.pop(timeout=0.25)
            except BaseException:
                # a transport failure is fatal for the lane: report it
                # (a bare exit would look clean to the collector while
                # this group's queued steps never settle)
                obs_events.EVENTS.emit(obs_events.LANE_ERROR, step=-1,
                                       group=group, stage="transport")
                send(LaneMsg("error", -1, group,
                             extras=traceback.format_exc()))
                return
            if snap is None:
                if area.closed and len(area) == 0:
                    return
                continue
            try:
                send(work(db, group, snap, tracer, t_pop, *args))
            except BaseException:
                obs_events.EVENTS.emit(obs_events.LANE_ERROR,
                                       step=snap.step, group=group,
                                       stage="work")
                send(LaneMsg("error", snap.step, group,
                             extras=traceback.format_exc()))
            finally:
                area.release(snap)
    finally:
        db.close()
        area.detach()
        send(LaneMsg("exit", None, group))


class Lanes:
    """One lane per contributor group, in one mode, for one job.

    :meth:`area` hands out a group's staging area and creates its lane
    on first use: HDep opens its groups up front, HProt's groups are
    known only once the state's sharding is seen. Lanes run from
    :meth:`start` on. Thread mode stages through ``area_cls`` with
    ``queue_capacity + workers + 1`` buffers; process mode through
    :class:`ShmStagingArea` with ``queue_capacity + 2`` slots. ``stop``
    never returns while a lane could still be writing.
    """

    def __init__(self, job: LaneJob, mode: str, *, root: str,
                 queue_capacity: int, policy: str, workers: int = 1,
                 area_cls=StagingArea, on_evict=None):
        self.job = job
        self.mode = check_mode(mode)
        self._root = root
        self._capacity = queue_capacity
        self._policy = policy
        self._workers = max(1, workers)
        self._area_cls = area_cls
        self._on_evict = on_evict
        #: group -> staging area
        self.stages: dict[int, object] = {}
        self._threads: dict[int, list[threading.Thread]] = {}
        self._procs: dict[int, object] = {}
        self._exited: set[int] = set()
        self._lock = threading.Lock()
        self._started = False
        self._stopping = False
        if self.mode == "process":
            self._ctx = multiprocessing.get_context("spawn")
            self._results = self._ctx.Queue()
            self._collector = threading.Thread(
                target=self._collect, name=f"{job.label}-collector",
                daemon=True)

    def area(self, group: int):
        """The group's staging area; its lane is made on first use."""
        with self._lock:
            area = self.stages.get(group)
            if area is None:
                area = self.stages[group] = self._open(group)
                if self._started:
                    self._start_lane(group)
            return area

    def _open(self, group: int):
        label, cap = self.job.label, self._capacity
        if self.mode == "thread":
            area = self._area_cls(capacity=cap, policy=self._policy,
                                  n_buffers=cap + self._workers + 1,
                                  on_evict=self._on_evict)
            self._threads[group] = [
                threading.Thread(target=self._thread_lane,
                                 args=(group, area),
                                 name=f"{label}-g{group}-{i}", daemon=True)
                for i in range(self._workers)]
            return area
        area = ShmStagingArea(capacity=cap, policy=self._policy,
                              n_slots=cap + 2, on_evict=self._on_evict,
                              mp_context=self._ctx)
        self._procs[group] = self._ctx.Process(
            target=_lane_main,
            args=(self.job.lane_work, self.job.lane_args, area.handle(),
                  self._root, group, self._results),
            name=f"{label}-lane-g{group}", daemon=True)
        return area

    def _start_lane(self, group: int) -> None:
        if self.mode == "thread":
            for t in self._threads[group]:
                t.start()
        else:
            self._procs[group].start()

    def start(self) -> "Lanes":
        with self._lock:
            self._started = True
            for group in self.stages:
                self._start_lane(group)
        if self.mode == "process":
            self._collector.start()
        return self

    def _thread_lane(self, group: int, area) -> None:
        job = self.job
        while True:
            t_pop = now_us() if TRACER.enabled else 0.0
            snap = area.pop(timeout=0.25)
            if snap is None:
                job.idle()
                if area.closed and len(area) == 0:
                    return
                continue
            try:
                job.work(group, snap, t_pop)
            finally:
                area.release(snap)
            job.idle()

    # ------------------------------------------------------- result intake
    def _collect(self) -> None:
        job = self.job
        while True:
            try:
                msg = self._results.get(timeout=0.25)
            except (ValueError, OSError):
                return   # results queue torn down under a stuck stop
            except queue.Empty:
                job.idle()
                with self._lock:
                    procs = dict(self._procs)
                if self._stopping and all(
                        g in self._exited or not p.is_alive()
                        for g, p in procs.items()):
                    return
                if not self._stopping:
                    self._check_lanes(procs)
                continue
            if msg.events:
                self._relay_events(msg.group, msg.events)
            if msg.spans:                # lane spans join the parent trace
                TRACER.ingest(msg.spans)
            if msg.tag == "exit":
                self._exited.add(msg.group)
            elif msg.tag == "error":
                job.failed(msg.group, RuntimeError(
                    f"{job.label} lane g{msg.group} failed at step "
                    f"{msg.step}:\n{msg.extras}"), step=msg.step)
                if msg.step < 0:
                    # fatal transport failure: the lane is exiting; stop
                    # producers from queueing (or blocking) behind it
                    self._close_area(msg.group)
            else:
                job.settle(msg)
            job.idle()

    def _relay_events(self, group: int, evs: list) -> None:
        """Land a lane's flight-recorder drain: into its own domain of
        the job's run ledger when one is bound, else into this process's
        ring so the events at least stay live-visible."""
        led = self.job.ledger
        if led is not None:
            from ..obs.ledger import lane_domain
            led.ingest_domain(lane_domain(group), {"events": evs})
        else:
            obs_events.EVENTS.ingest(evs)

    def _check_lanes(self, procs: dict) -> None:
        """Report lanes that died without reporting (crash semantics).

        Only a nonzero exit code is a crash: a lane that exited cleanly
        may have its "exit" message still queued.
        """
        for g, p in procs.items():
            if g not in self._exited and p.exitcode not in (None, 0):
                self._exited.add(g)
                self.job.failed(g, RuntimeError(
                    f"{self.job.label} lane g{g} died (exit code "
                    f"{p.exitcode}) without draining its staging area"),
                    exitcode=p.exitcode)
                # fail fast instead of deadlocking a block-policy
                # producer against a lane that will never pop again
                self._close_area(g)

    def _close_area(self, group: int) -> None:
        with self._lock:
            area = self.stages.get(group)
        if area is not None:
            area.close()

    # ------------------------------------------------------------ control
    def stop(self, timeout: float = 30.0) -> None:
        """Close every staging area and wait for the lanes to drain."""
        with self._lock:
            areas = list(self.stages.values())
            threads = [t for ts in self._threads.values() for t in ts]
            procs = list(self._procs.values())
        for area in areas:
            area.close()
        for t in threads:
            if t.ident is not None:      # skip never-started lanes
                t.join(timeout=timeout)
        if any(t.is_alive() for t in threads):
            # never close the db under a still-writing lane — a leaked
            # daemon thread beats a corrupted group file
            raise TimeoutError(
                f"{self.job.label} lanes did not stop; database left open")
        if self.mode == "thread":
            return
        killed = []
        for p in procs:
            if p.pid is None:            # never-started lane
                continue
            p.join(timeout=timeout)
            if p.is_alive():
                # a stuck lane is its own process: killing it cannot
                # corrupt the parent; its un-reported bytes stay
                # orphaned (no manifest references them)
                p.terminate()
                p.join(timeout=5.0)
                killed.append(p.name)
        self._stopping = True
        if self._collector.ident is not None:
            self._collector.join(timeout=timeout)
        for area in areas:
            area.unlink()
        self._results.close()
        self._results.join_thread()
        if killed:
            self.job.errors.append(TimeoutError(
                f"{self.job.label} lanes {killed} did not stop; "
                f"terminated (unreported work lost)"))

    def telemetry(self) -> dict:
        with self._lock:
            lanes = [t for ts in self._threads.values() for t in ts] + \
                list(self._procs.values())
        out = {"kind": self.mode, "n_lanes": len(lanes),
               "lanes_alive": sum(lane.is_alive() for lane in lanes)}
        if self.mode == "process":
            out["lanes_exited"] = len(self._exited)
        return out
