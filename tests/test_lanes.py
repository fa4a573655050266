"""The lane runtime (``insitu.lanes``) under both of its jobs — HDep's
engine and HProt's checkpoint manager — in both modes: a lane that never
drains, and producers pushing into an area that closed under them."""
import threading
import time

import numpy as np
import pytest

from repro.ckpt import AsyncCheckpointManager
from repro.ckpt.lanes import HProtJob
from repro.insitu import InTransitEngine, TensorNormReducer
from repro.insitu.engine import _HDepJob
from repro.insitu.lanes import MODES

JOBS = {"hdep": _HDepJob, "hprot": HProtJob}


def _hang_in_lane(*args):
    """Process-lane work that never returns (pickled by name)."""
    time.sleep(3600)


def _push(area, step):
    return area.push(step, {"payload": np.zeros(8, np.uint8)},
                     kind="tensors")


def _wait_for(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.02)


@pytest.fixture(params=[(j, m) for j in sorted(JOBS) for m in MODES],
                ids=lambda p: f"{p[0]}-{p[1]}")
def stuck(request, tmp_path, monkeypatch):
    """An engine or manager whose group-0 lane holds its first snapshot
    forever: the thread lane until ``release`` is set, the process lane
    until it is killed. Yields ``(owner, mode, area, release)``."""
    job, mode = request.param
    release = threading.Event()
    monkeypatch.setattr(JOBS[job], "work",
                        lambda self, group, snap, t_pop: release.wait(30))
    monkeypatch.setattr(JOBS[job], "lane_work", staticmethod(_hang_in_lane))
    root = str(tmp_path / "db")
    owner = InTransitEngine(root, [TensorNormReducer()], backend=mode,
                            policy="block").start() if job == "hdep" \
        else AsyncCheckpointManager(root, ncf=1, lane_backend=mode)
    area = owner._backend.area(0)
    assert _push(area, 1)
    _wait_for(lambda: area.stats.popped == 1, "the lane never popped")
    yield owner, mode, area, release
    release.set()
    if mode == "thread":
        owner.close()          # the freed lane drains, the db closes
        return
    if not owner._backend._stopping:
        owner._backend.stop(timeout=0.2)   # terminates the stuck lane
    owner.db.close()


def test_stuck_lane_stop(stuck):
    """stop() on a lane that never drains: a thread lane makes it raise
    and leave the database open; a process lane is terminated and its
    loss goes to the owner's errors."""
    owner, mode, _, release = stuck
    lanes = owner._backend
    if mode == "thread":
        with pytest.raises(TimeoutError, match="database left open"):
            lanes.stop(timeout=0.2)
        [lane] = lanes._threads[0]
        assert lane.is_alive()
        release.set()
        lane.join(timeout=10)
        assert not lane.is_alive()
        return
    t0 = time.monotonic()
    lanes.stop(timeout=0.2)
    assert time.monotonic() - t0 < 30
    assert not lanes._procs[0].is_alive()
    assert any(isinstance(e, TimeoutError) and "terminated" in str(e)
               for e in owner._errors), owner._errors


def test_push_into_closed_area_raises(stuck):
    """A producer blocked on a full area behind a stuck lane raises once
    the area closes, and a push into the closed area raises at once."""
    _, _, area, _ = stuck
    caught = []

    def produce():
        try:
            for step in range(2, 100):
                _push(area, step)
        except RuntimeError as e:
            caught.append(e)

    producer = threading.Thread(target=produce, daemon=True)
    producer.start()
    _wait_for(lambda: len(area) == area.capacity, "the queue never filled")
    time.sleep(0.3)
    assert producer.is_alive() and not caught   # blocked, not dropped
    area.close()
    producer.join(timeout=10)
    assert not producer.is_alive()
    assert len(caught) == 1 and "closed" in str(caught[0])
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="closed"):
        _push(area, 100)
    assert time.monotonic() - t0 < 1.0
