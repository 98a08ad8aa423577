"""Lightweight span tracer: where does a round's wall time actually go?

The ``round`` telemetry event carries a whole-round host/dispatch/device
split, but nothing below that granularity — when the host phase grows,
nothing says whether the data gather, the sampler, or the JSONL flush
grew. ``span("data_fetch")`` / ``span("round_launch")`` /
``span("device_wait")`` context managers mark the phases that own wall
time. The sites and their names are listed in ``PERF.md`` section 3.

Always on. Instrumentation sites call :func:`span` unconditionally and
the process-global tracer always records: by default into a bounded ring
(``DEFAULT_RING`` completed spans, oldest dropped first and counted), so
a process that drives ``FedRuntime`` and ``RoundPipeline`` with no driver
around them (the benchmark, a notebook) still holds the host side of its
last few hundred rounds in ``current().snapshot()`` and
:func:`summary`. A driver that owns a telemetry stream :func:`install`\\ s
a tracer of its own and drains it into batched ``span`` events at the
round-record cadence, which ``scripts/teleview.py timeline`` renders into
a perfetto/chrome-tracing ``trace.json``; :func:`uninstall` hands the
sites back to the default ring.

A span is ``{id, parent, round, name, ts, dur_s, tid, depth}`` plus
whatever the site attached (``runtime``: the ordinal of the ``FedRuntime``
that opened it; ``ready`` on a ``data_wait``). ``parent`` is the id of
the span that encloses it on its thread. ``round`` is the global round
the work is for, as :func:`set_round` last said on that thread
(``RoundPipeline`` says it on the worker thread before it fetches round
g and on the loop's thread when it is asked for round g), so the
``data_fetch`` of a round on one thread joins its ``data_wait`` and
``round_dispatch`` on another; None outside any round.

Always on the profiler's clock. Every span also enters
``jax.profiler.TraceAnnotation("fed:" + name)`` just outside its own two
clock reads, so any profiler trace of the process (``--profile_rounds``,
the benchmark's traced stretch) shows the host spans as ``fed:`` rows
beside the device's operations. With no trace running an annotation
costs under a microsecond. The ring's own ``ts`` is on ``perf_counter``;
a profiler session's clock starts at the session's start, so the two are
joined by name and order, not by time.

Importable without jax (``threading`` + ``time`` only; the annotation is
looked up on the first span and left out where jax is absent): the data
layer (``data/fed_dataset.py``) and the offline tooling reason about
spans without jax in the room.

Thread-safety: spans may open/close on any thread (the stack of open
spans and the round are per thread); the completed-span buffer is
lock-protected, and ``drain()`` swaps the buffer atomically.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, Optional

DEFAULT_RING = 8192
LONGEST = 10     # spans summary() hands back whole

# span ids: process-wide, in opening order (next() on a count is atomic
# under the interpreter lock)
_IDS = itertools.count(1)
# per thread: the open spans (innermost last) and the round in progress
_LOCAL = threading.local()
_ANNOTATION: Any = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, or False where jax is absent."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = False
    return _ANNOTATION


def set_round(rnd: Optional[int]) -> None:
    """Every span opened on this thread from here on carries ``rnd``."""
    _LOCAL.round = rnd


class _Span:
    """One live span (context manager). Records on exit only — an
    exception inside the span still produces the span, with the time it
    actually took."""

    __slots__ = ("_tracer", "_name", "_rec", "_stack", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._rec = attrs

    def set(self, **attrs) -> None:
        """Attach what only the span's body learns (``ready=...``)."""
        self._rec.update(attrs)

    def __enter__(self) -> "_Span":
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self._rec = {"id": next(_IDS),
                     "parent": stack[-1] if stack else None,
                     "round": getattr(_LOCAL, "round", None),
                     "name": self._name, "depth": len(stack), **self._rec}
        stack.append(self._rec["id"])
        self._stack = stack
        ann = _annotation()
        self._ann = ann("fed:" + self._name) if ann else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        # this span closed: whatever an abandoned child left goes too
        del self._stack[self._rec["depth"]:]
        self._tracer._record(self._rec, self._t0, t1 - self._t0)
        return False


class SpanTracer:
    """Buffers completed spans, for a periodic drain into the telemetry
    stream or as the process's ring.

    Spans carry ``ts`` (seconds since the tracer's epoch, measured on
    the monotonic ``perf_counter`` clock — NTP steps cannot reorder
    them), ``dur_s``, ``tid`` (a small per-tracer thread ordinal) and
    ``depth`` (nesting level within the thread) beside ``id``, ``parent``
    and ``round`` (module docstring). ``t0_wall`` anchors the monotonic
    epoch to unix time once, so offline tools can align spans with the
    events' absolute ``t`` fields.

    ``max_spans`` bounds the buffer: a tracer nobody drains keeps the
    newest ``max_spans`` and counts what fell out in ``dropped_total``.
    ``pop_dropped()`` returns the drops since it was last asked, so each
    ``span`` event reports the drops of ITS window — per-event counts sum
    to the true total.
    """

    def __init__(self, max_spans: int = 100_000):
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()
        self.max_spans = max_spans
        self.dropped_total = 0
        self._popped = 0
        self._buf: Deque[Dict[str, Any]] = collections.deque()
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}

    # ------------------------------------------------------------- recording

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _record(self, rec: Dict[str, Any], t0: float, dur: float) -> None:
        rec["ts"] = round(t0 - self.t0, 6)
        rec["dur_s"] = round(dur, 6)
        rec["tid"] = self._tid()
        with self._lock:
            self._buf.append(rec)
            if len(self._buf) > self.max_spans:
                self._buf.popleft()
                self.dropped_total += 1

    # --------------------------------------------------------------- reading

    def snapshot(self) -> List[Dict[str, Any]]:
        """The completed spans held now, oldest first (by the time they
        closed); nothing is cleared."""
        with self._lock:
            return list(self._buf)

    def drain(self) -> List[Dict[str, Any]]:
        """Return and clear the completed-span buffer (open spans land in
        a later drain)."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
            return out

    def pop_dropped(self) -> int:
        """Drops since the last pop (atomically reset)."""
        with self._lock:
            d, self._popped = self.dropped_total - self._popped, \
                self.dropped_total
            return d


# process-global tracer: instrumentation sites call tracing.span(name)
# unconditionally. The default ring records until a driver that owns a
# telemetry stream installs a tracer it drains, and again after.
_RING = SpanTracer(max_spans=DEFAULT_RING)
_TRACER: SpanTracer = _RING


def current() -> SpanTracer:
    return _TRACER


def install(tracer: Optional[SpanTracer] = None) -> SpanTracer:
    """Make ``tracer`` (or a fresh SpanTracer) the process-global tracer;
    returns it. Pair with :func:`uninstall` in a finally block."""
    global _TRACER
    if tracer is None:
        tracer = SpanTracer()
    _TRACER = tracer
    return tracer


def uninstall() -> None:
    """Back to the process's default ring (which keeps what it held)."""
    global _TRACER
    _TRACER = _RING


def span(name: str, **attrs) -> _Span:
    """Open a span on the current tracer. Usage:
    ``with tracing.span("data_fetch"): ...``"""
    return _TRACER.span(name, **attrs)


def summary() -> Dict[str, Any]:
    """Per name the count, total and longest of the completed spans the
    current tracer holds, and the ``LONGEST`` longest spans whole, with
    their ``round`` and ``parent``: where the host's time went, in one
    call."""
    spans = _TRACER.snapshot()
    names: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        row = names.setdefault(s["name"],
                               {"count": 0, "total_s": 0.0, "max_s": 0.0})
        row["count"] += 1
        row["total_s"] += s["dur_s"]
        row["max_s"] = max(row["max_s"], s["dur_s"])
    for row in names.values():
        row["total_s"] = round(row["total_s"], 6)
    return {"spans": len(spans), "dropped": _TRACER.dropped_total,
            "names": names,
            "longest": sorted(spans, key=lambda s: -s["dur_s"])[:LONGEST]}
