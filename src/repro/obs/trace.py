"""Per-step span tracing with cross-process context propagation.

Every pipeline stage (producer ``submit`` → staging enqueue/dequeue →
lane ``reduce`` → device transfer → domain ``write`` → manifest
``commit``) opens a span. Spans carry ``trace_id`` (one per pipeline
step), ``span_id``, and ``parent_id``; within a thread, parentage is
implicit via a thread-local span stack. Across process lanes the parent
context rides the existing shm descriptor JSON header (a two-key dict
from :meth:`Tracer.context`, restored lane-side with ``parent=``), and
finished lane spans are shipped back over the results queue and
:meth:`Tracer.ingest`-ed into the parent's buffer.

The export format is Chrome trace / Perfetto JSON (``traceEvents`` with
complete ``ph:"X"`` events): ``write_chrome_trace(path)`` then
chrome://tracing or https://ui.perfetto.dev loads it directly.

Tracing is OFF by default — ``span()`` returns a shared no-op object
and costs one attribute read; ``launch/insitu.py --trace-out`` enables
the global ``TRACER`` for a run.

Two clocks. Span timestamps are microseconds since the unix epoch (the
Chrome-trace export and the run ledger read them). A JAX profiler trace
counts from the start of its own session, so a span's ``ts`` cannot be
laid over the device's ops. :meth:`Tracer.enable` therefore also makes
every ``with``-opened span enter a ``jax.profiler.TraceAnnotation`` of
the same name: while a profiler session records, each such span shows on
the host plane of its trace, on the profiler's clock, on the thread that
opened it. Spans logged after the fact with :meth:`Tracer.record` — the
process lanes' ``stage.pop``/``reduce``/``write``/``ckpt.write`` and the
thread lanes' ``stage.pop`` — and spans :meth:`Tracer.ingest`-ed from
another process keep only the epoch clock.

``enable()`` also listens to JAX's compile events (``jax.monitoring``):
each backend compile becomes a ``jit.compile`` span (``args["fun"]``)
under whatever span the compiling thread has open, so a trace shows
which step recompiled. ``disable()`` removes the listeners; a disabled
tracer costs nothing, not even on a compile. A tracer constructed with
``enabled=True`` (the process lanes' local tracers) records spans but
hooks neither the profiler nor the compile events.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import random
import threading
import time

_EPOCH_NS = time.time_ns() - time.perf_counter_ns()

#: the ``jax.monitoring`` event that brackets one backend compile: a
#: scalar (its start) on entry, a duration on exit, both on the
#: compiling thread and both carrying ``fun_name``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _now_us() -> float:
    """Microseconds since the unix epoch, monotonic within the process."""
    return (_EPOCH_NS + time.perf_counter_ns()) / 1e3


def _new_id() -> str:
    # a PRNG draw, not uuid4: os.urandom gives up the GIL, and a span
    # opened on a busy host then waits up to a switch interval to get it
    # back, outside any span (``random`` reseeds itself in a forked child)
    return f"{random.getrandbits(64):016x}"


class Span:
    """One timed unit of pipeline work (Chrome-trace complete event)."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "ts", "dur", "args", "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 trace_id: str, parent_id: str | None, args=None):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.ts = _now_us()
        self.dur = 0.0
        self.args = dict(args) if args else {}
        self._tracer = tracer
        self._annotation = None

    def set(self, **kw) -> None:
        self.args.update(kw)

    def __enter__(self):
        annotate = self._tracer._annotate
        if annotate is not None:
            self._annotation = annotate(self.name)
            self._annotation.__enter__()
        self._tracer._push(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self._tracer._pop(self)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        return False

    def context(self) -> dict:
        """Wire form of this span as a parent: rides JSON headers."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def as_dict(self) -> dict:
        return {"name": self.name, "cat": self.cat,
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "pid": os.getpid(),
                "tid": threading.get_ident() % 2**31,
                "ts": self.ts, "dur": self.dur, "args": self.args}


class _NoopSpan:
    """Shared do-nothing span: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw) -> None:
        pass

    def context(self):
        return None


_NOOP = _NoopSpan()


#: default retained-span window; a long ledger-instrumented run keeps
#: only the newest spans in memory (older ones were already flushed to
#: the run ledger, or weren't wanted at all)
DEFAULT_MAX_SPANS = 100_000


class Tracer:
    """Collects finished spans; thread-local stack gives implicit parents.

    The span buffer is bounded (``max_spans``, a deque window): once a
    run outgrows it the oldest spans fall off and ``spans_dropped``
    counts them. ``write_chrome_trace``/``export`` keep their exact
    semantics on the retained window; incremental consumers (the run
    ledger) use :meth:`drain_since` marks and therefore see every span
    as long as they drain faster than the window turns over.
    """

    def __init__(self, enabled: bool = False,
                 max_spans: int = DEFAULT_MAX_SPANS):
        self.enabled = enabled
        self._max_spans = int(max_spans)
        self._spans: collections.deque[dict] = \
            collections.deque(maxlen=self._max_spans)
        self._appended = 0          # lifetime spans, incl. fallen-off
        self._lock = threading.Lock()
        self._tls = threading.local()
        #: ``jax.profiler.TraceAnnotation`` while enabled by enable()
        self._annotate = None
        #: enable() registered the compile listeners
        self._hooked = False

    # --------------------------------------------------------- lifecycle
    def enable(self) -> None:
        """Start recording; hook the profiler and the compile events."""
        self.enabled = True
        if self._hooked:
            return
        try:
            import jax.monitoring
            import jax.profiler
        except ImportError:      # a stdlib-only process: spans alone
            return
        self._annotate = jax.profiler.TraceAnnotation
        self._hooked = True
        jax.monitoring.register_scalar_listener(self._on_compile_start)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile_end)

    def disable(self) -> None:
        """Stop recording; unhook what enable() hooked."""
        self.enabled = False
        self._annotate = None
        if not self._hooked:
            return
        import jax.monitoring
        self._hooked = False
        jax.monitoring.unregister_scalar_listener(self._on_compile_start)
        jax.monitoring.unregister_event_duration_listener(
            self._on_compile_end)

    def _on_compile_start(self, event: str, value, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        sp = self.span("jit.compile", cat="jax",
                       args={"fun": kw.get("fun_name", "")})
        sp.__enter__()
        st = getattr(self._tls, "compiles", None)
        if st is None:
            st = self._tls.compiles = []
        st.append(sp)

    def _on_compile_end(self, event: str, secs: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        st = getattr(self._tls, "compiles", None)
        if st:
            st.pop().__exit__(None, None, None)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._appended = 0

    def set_max_spans(self, n: int) -> None:
        """Resize the retained window (keeps the newest spans)."""
        with self._lock:
            self._max_spans = int(n)
            self._spans = collections.deque(self._spans,
                                            maxlen=self._max_spans)

    @property
    def max_spans(self) -> int:
        return self._max_spans

    @property
    def spans_dropped(self) -> int:
        """Spans that fell off the bounded window (lifetime count)."""
        with self._lock:
            return self._appended - len(self._spans)

    # ------------------------------------------------------------- spans
    def span(self, name: str, cat: str = "insitu", parent=None,
             args=None):
        """Open a span. ``parent`` may be a wire dict from ``context()``.

        Disabled tracers hand back a shared no-op, so call sites don't
        need their own enabled checks.
        """
        if not self.enabled:
            return _NOOP
        if parent is not None:
            trace_id = parent["trace_id"]
            parent_id = parent["span_id"]
        else:
            cur = self._current()
            if cur is not None:
                trace_id, parent_id = cur.trace_id, cur.span_id
            else:
                trace_id, parent_id = _new_id(), None
        return Span(self, name, cat, trace_id, parent_id, args)

    def record(self, name: str, t0_us: float, t1_us: float,
               cat: str = "insitu", parent=None, args=None) -> dict | None:
        """Log an already-measured interval (timestamps from ``now_us``)."""
        if not self.enabled:
            return None
        span = self.span(name, cat, parent=parent, args=args)
        span.ts = t0_us
        span.dur = max(0.0, t1_us - t0_us)
        rec = span.as_dict()
        with self._lock:
            self._spans.append(rec)
            self._appended += 1
        return rec

    def context(self) -> dict | None:
        """Wire dict of the innermost open span (None when disabled)."""
        cur = self._current()
        return cur.context() if cur is not None else None

    def ingest(self, spans) -> None:
        """Merge span dicts produced elsewhere (e.g. a process lane)."""
        if not spans:
            return
        with self._lock:
            self._spans.extend(spans)
            self._appended += len(spans)

    # ----------------------------------------------------------- exports
    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    def drain_since(self, mark: int) -> tuple[int, list[dict]]:
        """Spans appended after ``mark``; returns ``(new_mark, spans)``.

        ``mark`` is an opaque cursor (the lifetime append count from a
        previous call; start at 0). Spans that both arrived and fell
        off the bounded window between two drains are lost — they still
        show in :attr:`spans_dropped`. A cursor ahead of the buffer
        (e.g. after :meth:`clear`) resyncs to the full window.
        """
        with self._lock:
            total = self._appended
            if mark > total:      # buffer was cleared since that mark
                mark = total - len(self._spans)
            n_new = min(total - mark, len(self._spans))
            if n_new <= 0:
                return total, []
            start = len(self._spans) - n_new
            return total, list(itertools.islice(
                self._spans, start, len(self._spans)))

    def export(self) -> dict:
        """Chrome-trace JSON object (load in chrome://tracing/Perfetto)."""
        events = []
        for s in self.spans():
            events.append({
                "name": s["name"], "cat": s["cat"], "ph": "X",
                "pid": s["pid"], "tid": s["tid"],
                "ts": s["ts"], "dur": s["dur"],
                "args": {**s["args"], "trace_id": s["trace_id"],
                         "span_id": s["span_id"],
                         "parent_id": s["parent_id"]}})
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> int:
        """Write the export to ``path``; returns the span count."""
        doc = self.export()
        with open(path, "w") as fh:
            json.dump(doc, fh)
        return len(doc["traceEvents"])

    # ----------------------------------------------------------- internal
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _current(self):
        st = self._stack()
        return st[-1] if st else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        span.dur = _now_us() - span.ts
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        else:                      # unbalanced exit: drop just this span
            try:
                st.remove(span)
            except ValueError:
                pass
        with self._lock:
            self._spans.append(span.as_dict())
            self._appended += 1


def now_us() -> float:
    """Public clock for ``Tracer.record`` call sites."""
    return _now_us()


#: process-global tracer: pipeline call sites trace through this; it is
#: disabled (no-op spans) unless a CLI/test enables it
TRACER = Tracer(enabled=False)
