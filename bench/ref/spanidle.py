"""The device's idle seconds in the window, put down to the program's spans.

The program's tracer opens a ``jax.profiler.TraceAnnotation`` under the
name of each span it opens, so its spans sit on the host planes of the
profiler trace, on the device ops' clock. This module reads the trace
that ``run_cell.run`` keeps under its scratch directory until the
per-layer readers are done, keeps the window and the host events named
in :data:`PROGRAM_SPANS`, and labels each idle gap of each device with
the innermost of those spans open at the gap's midpoint (the shortest
one, ties by name: the rule of ``devtrace.reduce``), else ``"none"``.

``devtrace.reduce`` searches every span for every gap; a lod7 window has
some ten thousand of each, so :func:`idle_by_span` sweeps the sorted
gaps with a heap of open spans instead, which gives the same labels.
Each trace file is parsed once, however many readers ask.
"""
from __future__ import annotations

import heapq
import os

from . import devtrace, intervals

#: every span the program opens with ``with``, so that shows on the
#: profiler trace (``Tracer.record``-ed spans never do)
PROGRAM_SPANS = (
    "submit", "stage.push", "stage.wait", "stage.upload",
    "reduce", "device.transfer", "device.dispatch", "device.pull",
    "write", "manifest.commit", "jit.compile",
    "ckpt.snapshot", "ckpt.stage", "ckpt.pull", "ckpt.encode", "ckpt.crc",
    "ckpt.enqueue", "ckpt.write", "ckpt.commit")

#: the in-transit lane's spans: the device waits on the lane's host work
LANE_SPANS = ("reduce", "device.transfer", "device.dispatch", "device.pull",
              "write", "manifest.commit", "jit.compile")

#: the HProt gather's parts
GATHER_SPANS = ("ckpt.pull", "ckpt.encode", "ckpt.crc", "ckpt.enqueue")

_cache: dict = {}


def idle_by_span(trace: dict) -> dict | None:
    """``{label: idle seconds}`` over the window, averaged over the
    devices that ran an op in it; ``None`` without a window or such an
    op. ``trace`` is what ``devtrace.load`` returns."""
    wins = [(a, b) for name, a, b in trace["host"]
            if name == devtrace.WINDOW]
    if not wins:
        return None
    lo, hi = wins[0]
    spans = sorted((a, b, name) for name, a, b in trace["host"]
                   if name != devtrace.WINDOW)
    idle, n_dev = {}, 0
    for dev_ops in trace["devices"].values():
        inside = [(a, b) for _, a, b in dev_ops if b > lo and a < hi]
        if not inside:
            continue
        n_dev += 1
        heap, i = [], 0
        for a, b in intervals.gaps(inside, lo, hi):
            mid = (a + b) / 2
            while i < len(spans) and spans[i][0] <= mid:
                sa, sb, name = spans[i]
                heapq.heappush(heap, (sb - sa, name, sb))
                i += 1
            # midpoints only grow: a span closed at the top never reopens
            while heap and heap[0][2] <= mid:
                heapq.heappop(heap)
            label = heap[0][1] if heap else "none"
            idle[label] = idle.get(label, 0.0) + (b - a)
    if n_dev == 0:
        return None
    return {k: v / n_dev for k, v in idle.items()}


def trace_path() -> str | None:
    """The run's profiler trace, while the readers run."""
    import run_cell
    return devtrace.find_xplane(os.path.join(run_cell.SCRATCH, "trace"))


def _read(path: str) -> tuple[dict | None, set]:
    trace = devtrace.load(path, PROGRAM_SPANS)
    return idle_by_span(trace), {name for name, _, _ in trace["host"]}


def idle_under(names) -> float | None:
    """Idle seconds per device whose innermost program span is one of
    ``names``; ``None`` when the run's trace holds none of those spans
    (a program that does not annotate them has nothing to read)."""
    path = trace_path()
    if path is None:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(path)
    table, present = _cache[key]
    if table is None or not present & set(names):
        return None
    return sum(v for k, v in table.items() if k in names)
