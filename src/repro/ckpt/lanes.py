"""HProt's writer-lane job for the lane runtime (``insitu/lanes.py``).

The manager's gather thread streams encoded shards into one staging
area per Hercule contributor group, and a *writer lane* per group
drains it: append to the group's files, publish to the page cache,
report the :class:`~repro.hercule.database.Record` home. Lanes never
fsync and never commit: durability belongs to the manifest committer
(``HerculeDB.commit_context``), the split the multi-domain in-transit
engine uses too.

In thread mode each group stages through a :class:`ReferenceStagingArea`,
which holds each payload by reference: the gather's raw shard is the
pulled host buffer itself, never copied on the way. In process mode the
runtime's shared-memory slab takes one copy of each payload. The manager
runs both with ``policy="block"``: checkpoints are lossless, and
backpressure stalls the *gather thread*, never the train step.

Crash semantics: a lane failure or a dead lane process goes through
``manager._lane_failed``, which fails every in-flight step (their
records can never all land) so no manifest commits for them.
"""
from __future__ import annotations

import time

from ..hercule.database import DomainWriter, HerculeDB, Record
from ..insitu.lanes import LaneJob, LaneMsg
from ..insitu.staging import StagingArea
from ..obs.trace import TRACER, now_us


def _write_shard(db: HerculeDB, snap) -> list[Record]:
    """Append one staged shard to its group files; returns its records."""
    d = snap.meta
    w = DomainWriter(db, snap.step)
    w.write_bytes(int(d["domain"]), d["rec_name"],
                  bytes(snap.arrays["payload"]),
                  dtype=d["dtype"], shape=tuple(d["shape"]),
                  codec=d["codec"], meta=d["rec_meta"])
    db.flush_domain(int(d["domain"]), sync=False)
    return w.records


class _HeldBuffers:
    """Buffer set that stages the pushed host arrays themselves.

    Holds nothing on ``self`` between fills: the snapshot owns the only
    reference, and :meth:`ReferenceStagingArea.release` drops it, so
    host memory does not grow with the pool."""

    def fill(self, arrays: dict):
        out = dict(arrays)
        return out, 0, 0, sum(a.nbytes for a in out.values())


class ReferenceStagingArea(StagingArea):
    """:class:`StagingArea` that stages by reference, not by copy.

    For producers whose pushed arrays are private and never written
    again — the gather's payloads: a fresh host pull, a host-cut leaf or
    a copied non-jax leaf, or a delta codec's output. The base area's
    pooled copy is for producers that reuse or donate their arrays
    after ``push``, as the HDep engine's do; the gather does neither.
    """

    BUFFER_SET = _HeldBuffers

    def release(self, snap) -> None:
        super().release(snap)
        snap.arrays = {}


def _write_in_lane(db, group, snap, tracer, t_pop) -> LaneMsg:
    """Process-lane work: append one shard, send its records home."""
    w0 = now_us()
    records = _write_shard(db, snap)
    w1 = now_us()
    tctx = snap.meta.get("_trace")
    if tctx is not None:
        tracer.record("ckpt.write", w0, w1, cat="ckpt", parent=tctx,
                      args={"step": snap.step, "group": group})
    return LaneMsg("done", snap.step, group, records=records,
                   timings=((w1 - w0) / 1e6,))


class HProtJob(LaneJob):
    """HProt's lane work: append each staged shard to its group files
    and report the records to the manager's commit countdown."""

    label = "hprot"
    lane_work = staticmethod(_write_in_lane)

    def __init__(self, manager):
        self.manager = manager
        self.errors = manager._errors

    def work(self, group, snap, t_pop):
        mgr = self.manager
        try:
            t0 = time.perf_counter()
            with TRACER.span("ckpt.write", cat="ckpt",
                             parent=snap.meta.get("_trace"),
                             args={"step": snap.step, "group": group}):
                records = _write_shard(mgr.db, snap)
            mgr._shard_landed(snap.step, group, records,
                              write_seconds=time.perf_counter() - t0)
        except BaseException as e:   # noqa: BLE001 — surfaced on wait
            mgr._lane_failed(group, e)

    def settle(self, msg):
        self.manager._shard_landed(msg.step, msg.group, msg.records,
                                   write_seconds=msg.timings[0])

    def failed(self, group, exc, *, step=None, exitcode=None):
        self.manager._lane_failed(group, exc)
