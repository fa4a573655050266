"""The tracer's spans on the profiler's clock, and the spans that split
the staging upload, the device transfer and the HProt gather (DESIGN.md
§15, §16).

Every ``with``-opened span of an enabled ``TRACER`` enters a
``jax.profiler.TraceAnnotation`` of its own name, so a profiler trace
holds it on its host plane beside the device's ops; backend compiles
become ``jit.compile`` spans under the span open on the compiling
thread; a disabled tracer hooks nothing.
"""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax._src import monitoring as jax_monitoring

from repro.ckpt import AsyncCheckpointManager
from repro.insitu import InTransitEngine
from repro.insitu.reducers import LevelHistogramReducer, SliceReducer
from repro.insitu.staging import StagingArea
from repro.obs import TRACER
from repro.obs.trace import _NOOP
from repro.sim import amrgen, fields


@pytest.fixture()
def tracing():
    """Enable the global tracer for one test, restore after."""
    TRACER.clear()
    TRACER.enable()
    yield TRACER
    TRACER.disable()
    TRACER.clear()


def _named(name):
    return [s for s in TRACER.spans() if s["name"] == name]


def _host_events(trace_dir, names):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_span_lands_on_the_profiler_host_plane(tracing, tmp_path):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    f(jnp.ones(8)).block_until_ready()       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            with TRACER.span("test.inner"):
                f(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path), {"test.outer", "test.inner"})
    (o0, o1), = ev["test.outer"]
    (i0, i1), = ev["test.inner"]
    assert o0 <= i0 < o0 + 1_000_000           # within 1 ms, in ns
    assert i1 <= o1
    # the tracer's own record keeps the epoch clock
    (sp,) = _named("test.inner")
    assert sp["ts"] > 1e15


def test_record_keeps_to_the_tracer_clock(tracing, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            t0 = time.time() * 1e6
            TRACER.record("test.recorded", t0, t0 + 10.0)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path), {"test.outer", "test.recorded"})
    assert "test.outer" in ev and "test.recorded" not in ev
    assert len(_named("test.recorded")) == 1


def test_ring_full_wait_and_upload_are_apart(tracing):
    area = StagingArea(capacity=1, policy="block")
    big = {"x": np.arange(1 << 20, dtype=np.float64)}
    assert area.push(1, big)                  # the ring is now full
    held = 0.3

    def consumer():
        time.sleep(held)
        snap = area.pop(timeout=5.0)
        area.release(snap)
    t = threading.Thread(target=consumer)
    t.start()
    with TRACER.span("test.producer") as prod:
        assert area.push(2, big)
    t.join()
    area.close()
    waits, uploads = _named("stage.wait"), _named("stage.upload")
    assert len(uploads) == 2
    mine = [s for s in waits if s["parent_id"] == prod.span_id]
    assert mine and sum(s["dur"] for s in mine) >= 0.8 * held * 1e6
    last_wait = max(s["ts"] + s["dur"] for s in mine)
    second = max(uploads, key=lambda s: s["ts"])
    assert second["parent_id"] == prod.span_id
    assert second["ts"] >= last_wait          # the copy after the wait
    assert second["dur"] < 0.5 * held * 1e6


def test_checkpoint_stage_has_its_four_parts(tracing, tmp_path):
    # the stage's time outside its parts is the spans' own bookkeeping,
    # a few hundred microseconds a tensor however short the parts are
    # (on the raw path only the CRC is long): an absolute budget of
    # 5 ms a tensor, summed over the tensors so that one scheduling
    # hiccup on a loaded host does not decide it. At 64 MB a tensor,
    # one byte copy of a tensor outside the parts overruns it.
    rng = np.random.default_rng(3)
    state = {f"w{i}": jnp.asarray(rng.standard_normal((4096, 4096),
                                                      np.float32))
             for i in range(2)}
    m = AsyncCheckpointManager(str(tmp_path / "ck"), ncf=2)
    m.save(1, state)
    m.wait()
    m.close()
    stages = _named("ckpt.stage")
    assert len(stages) == 2
    kids: dict = {}
    for s in TRACER.spans():
        kids.setdefault(s["parent_id"], []).append(s)
    outside = 0
    for st in stages:
        parts = sorted(kids[st["span_id"]], key=lambda s: s["ts"])
        assert [p["name"] for p in parts] == [
            "ckpt.pull", "ckpt.encode", "ckpt.crc", "ckpt.enqueue"]
        for a, b in zip(parts, parts[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        outside += st["dur"] - sum(p["dur"] for p in parts)
    assert outside < 5_000 * len(stages)                     # µs


def test_compile_span_opens_under_the_current_span(tracing):
    def fresh_fn(x):
        return jnp.sin(x) * 2.0
    f = jax.jit(fresh_fn)
    with TRACER.span("test.step") as step:
        f(jnp.ones(13)).block_until_ready()
    compiles = [s for s in _named("jit.compile")
                if "fresh_fn" in s["args"]["fun"]]
    assert len(compiles) == 1
    assert compiles[0]["parent_id"] == step.span_id
    assert compiles[0]["dur"] > 0
    n = len(_named("jit.compile"))
    with TRACER.span("test.step"):
        f(jnp.ones(13)).block_until_ready()   # cached: no compile
    assert len(_named("jit.compile")) == n


def test_disabled_tracer_hooks_nothing():
    TRACER.clear()
    assert not TRACER.enabled
    assert TRACER.span("x") is _NOOP
    hooks = (jax_monitoring.get_scalar_listeners()
             + jax_monitoring.get_event_duration_listeners())
    assert not [h for h in hooks if getattr(h, "__self__", None) is TRACER]
    jax.jit(lambda x: x - 7.0)(jnp.ones(17)).block_until_ready()
    assert TRACER.spans() == []
    TRACER.enable()
    try:
        TRACER.enable()                        # idempotent
        hooks = (jax_monitoring.get_scalar_listeners()
                 + jax_monitoring.get_event_duration_listeners())
        assert len([h for h in hooks
                    if getattr(h, "__self__", None) is TRACER]) == 2
    finally:
        TRACER.disable()
        TRACER.clear()
    hooks = (jax_monitoring.get_scalar_listeners()
             + jax_monitoring.get_event_duration_listeners())
    assert not [h for h in hooks if getattr(h, "__self__", None) is TRACER]
    assert TRACER.span("x") is _NOOP


def test_device_transfer_splits_dispatch_and_pull(tracing, tmp_path):
    tree = amrgen.generate_tree(fields.sedov(), min_level=2, max_level=4,
                                threshold=1.2)
    eng = InTransitEngine(str(tmp_path / "db"), [
        SliceReducer(field="density", axis=2, position=0.5, resolution=32),
        LevelHistogramReducer(field="density", bins=16, lo=0.0, hi=8.0),
    ], device_reduce=True).start()
    assert eng.submit(1, tree)
    eng.close()
    by_id = {s["span_id"]: s for s in TRACER.spans()}
    transfers = _named("device.transfer")
    assert len(transfers) == 2
    for tr in transfers:
        kids = sorted((s for s in TRACER.spans()
                       if s["parent_id"] == tr["span_id"]),
                      key=lambda s: s["ts"])
        assert [k["name"] for k in kids] == ["device.dispatch",
                                             "device.pull"]
        assert by_id[tr["parent_id"]]["name"] == "reduce"
    (upload,) = _named("stage.upload")
    assert by_id[upload["parent_id"]]["name"] == "stage.push"
