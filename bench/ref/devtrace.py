"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Device planes are named ``/device:<PLATFORM>:<n>``; their ``XLA Ops``
line holds one event per executed operation. Host planes hold the
``jax.profiler.TraceAnnotation`` spans that the drivers open around
their calls, among them ``bench.window`` around the measured window.
All times come back in seconds on the trace's own clock.
"""
from __future__ import annotations

import glob
import os

from . import intervals

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def op_name(text: str) -> str:
    """An op's HLO name (``slice_raster.1``) from the trace's event name,
    which on a TPU is the op's whole HLO text (``%slice_raster.1 = ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, labels=()) -> dict:
    """``{"devices": {plane: [(name, t0, t1), ...]}, "host": [(name, t0,
    t1), ...]}`` from one ``.xplane.pb``: the ops of each device plane,
    and the host events named :data:`WINDOW` or in ``labels``."""
    keep = {WINDOW, *labels}
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events if ev.name in keep)
    return {"devices": devices, "host": host}


def reduce(trace: dict) -> dict | None:
    """The window's device numbers, or ``None`` when the trace holds no
    window or no device operation inside it.

    ``busy_s`` is the union of the op intervals inside the window,
    averaged over the device planes that ran anything; ``ops`` maps each
    op name to its summed seconds and call count over all devices;
    ``idle`` maps each host annotation (the innermost one open at a gap's
    midpoint, else ``"none"``) to the idle seconds it covers, averaged
    over the devices.
    """
    wins = [(a, b) for name, a, b in trace["host"] if name == WINDOW]
    if not wins:
        return None
    lo, hi = wins[0]
    spans = [(name, a, b) for name, a, b in trace["host"] if name != WINDOW]
    busy, ops, idle, n_dev = 0.0, {}, {}, 0
    for dev_ops in trace["devices"].values():
        inside = [(name, a, b) for name, a, b in dev_ops
                  if b > lo and a < hi]
        if not inside:
            continue
        n_dev += 1
        busy += intervals.union(intervals.clip(
            [(a, b) for _, a, b in inside], lo, hi))
        for name, a, b in inside:
            d = min(b, hi) - max(a, lo)
            tot, cnt = ops.get(name, (0.0, 0))
            ops[name] = (tot + d, cnt + 1)
        for a, b in intervals.gaps([(a, b) for _, a, b in inside], lo, hi):
            mid = (a + b) / 2
            open_ = [(sb - sa, name) for name, sa, sb in spans
                     if sa <= mid < sb]
            label = min(open_)[1] if open_ else "none"
            idle[label] = idle.get(label, 0.0) + (b - a)
    if n_dev == 0:
        return None
    return {"window_s": hi - lo, "busy_s": busy / n_dev, "devices": n_dev,
            "ops": ops, "idle": {k: v / n_dev for k, v in idle.items()}}


def top(table: dict, n: int = 10) -> list:
    """The ``n`` largest entries of a ``name -> seconds`` (or ``name ->
    (seconds, count)``) table as ``[name, seconds]`` pairs."""
    rows = [(name, v[0] if isinstance(v, tuple) else v)
            for name, v in table.items()]
    rows.sort(key=lambda r: -r[1])
    return [[name, secs] for name, secs in rows[:n]]
