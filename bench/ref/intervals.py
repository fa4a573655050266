"""Union-of-intervals arithmetic (a copy of ``obs.attrib.union_seconds``,
generalised to any unit): parallel lanes that are open at once count as
one stretch of wall time."""
from __future__ import annotations


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union(intervals) -> float:
    """Total length covered by ``intervals``."""
    return sum(b - a for a, b in merge(intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """``intervals`` cut to ``[lo, hi]``; empty pieces dropped."""
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``intervals`` leave uncovered."""
    out, t = [], lo
    for a, b in merge(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def self_time(span: tuple[float, float], children) -> float:
    """Length of ``span`` less the part its children cover."""
    lo, hi = span
    return (hi - lo) - union(clip(children, lo, hi))
