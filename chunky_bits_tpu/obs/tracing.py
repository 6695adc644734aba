"""Request spans: one API, two sinks.

:func:`span` marks one boundary of the served path — a context manager
that also works across ``await`` — opened when the work starts and
closed when it ends.  Where it lands:

* **The profiler's trace.**  While a JAX profiler session is active in
  the process (``jax.profiler.start_trace``), every span is a
  ``TraceAnnotation`` carrying its name and short args (``req``,
  ``bytes``, ``parts``, ...), on the same clock as the device's
  operations in the same ``.xplane.pb``.  The session itself is the
  switch: there is no option.  This module never imports jax; it asks
  the profiler only once something else has imported it.
* **The slowest-N buffer.**  With ``tunables.trace_slow_ms`` /
  ``$CHUNKY_BITS_TPU_TRACE_SLOW_MS`` > 0 (read at gateway app build),
  each request's spans are kept on its :class:`Trace` as flat
  ``(name, plane, start, duration, outcome)`` records, and requests at
  least that slow are filed in the buffer served at gateway
  ``GET /debug/traces`` with per-plane totals.  Planes: ``gateway``
  (the request envelope), ``queue`` (admission, batcher and pipeline
  waits), ``network`` (client socket, chunk and metadata I/O),
  ``compute`` (encode and reconstruct), ``host`` (pipeline jobs),
  ``device`` (codec dispatches).

Request identity: the gateway's access-log middleware mints a trace id
(or accepts the client's via ``X-Chunky-Trace``) whenever either sink is
on and parks the request's :class:`Trace` in a ``ContextVar``; asyncio
copies the context into every task the handler spawns, and the host
pipeline carries it across its worker threads by capture-at-submit
(``_Job.__init__``), so every span of a request carries its id as
``req``.  Spans serving many requests (batcher groups, their codec
dispatches) run in a context of their own and carry ``parts`` instead.

Off — no profiler session, ``trace_slow_ms`` 0 — a span costs one
``ContextVar.get`` and at most one enabled-check, and returns a shared
no-op object.

The span catalog (names are stable: benchmark readers select by name):

==========================  ==============================================
``gateway.<method>``        one request, middleware to response (req,
                            bytes, status)
``gateway.put.admit``       PUT admission: QoS write grant, ingest slot
``gateway.get.send``        one body write to the client
``writer.receive``          the read loop waiting on body bytes (bytes)
``writer.stage.<source>``   a staging block taken from the writer's pool:
                            ``reused`` or ``fresh`` (allocated)
``writer.encode``           a staged block's parity and digests (parts,
                            bytes)
``meta.write``/``meta.read``  file-reference publish (with fsync) / load
``dest.write``              one chunk write at its location (bytes)
``dest.publish``            a local chunk's publication in its worker
                            thread (bytes)
``reader.fetch``            one chunk's verified fetch (bytes)
``reader.reconstruct``      a part's await on the decode batcher
``batching.<op>.queue``     batcher submit to group start
``batching.<op>``           one coalesced group (parts, bytes)
``host.<stage>[.queue]``    a host-pipeline job / its queue wait
``codec.h2d``               host-to-device transfer (bytes)
``codec.launch``            the jitted codec call
``codec.collect``           device wait plus device-to-host copy (bytes)
``codec.hash_join``         the wait for ingest hash jobs
==========================  ==============================================
"""

from __future__ import annotations

import contextvars
import heapq
import itertools
import os
import sys
import threading
from typing import Optional

#: the clock seam (canonical surface cluster/clock.py; utils-side
#: import for cycle hygiene): slowest-N span starts come off this
#: clock, so the trace birth stamp the offsets subtract from must come
#: off the SAME clock — inside a virtual-time simulation a real-clock t0
#: would turn every start_ms into timebase-mixed garbage
from chunky_bits_tpu.utils import clock as _clock

#: the active trace for this context; None = untraced.  A ContextVar,
#: not module state: every asyncio task gets its own copy, worker
#: threads read None unless a job carried a captured trace.
_CURRENT: "contextvars.ContextVar[Optional[Trace]]" = \
    contextvars.ContextVar("cb_trace", default=None)

#: bound on spans per trace — a pathological fan-out (thousands of
#: chunk fetches) must not make one trace unbounded; drops are counted
#: on the trace itself
MAX_SPANS = 256

#: traces kept in the slowest-N buffer
BUFFER_CAPACITY = 64

#: accepted ``X-Chunky-Trace`` shape: short, printable, header-safe,
#: and free of the profiler's metadata separators (``#``, ``,``, ``=``)
_MAX_ID_LEN = 64
_ID_REJECTS = frozenset('"\\#,=')

#: slowest-N plane per span name (full name first, then its first
#: dotted part); see the module docstring
# lint: loop-shared-ok a constant table, never written after import
_PLANES = {
    "gateway": "gateway", "gateway.put.admit": "queue",
    "gateway.get.send": "network", "writer.receive": "network",
    "writer.encode": "compute", "meta": "network", "dest": "network",
    "reader.fetch": "network", "reader.reconstruct": "compute",
    "batching": "compute", "host": "host", "codec": "device",
}


def _plane(name: str) -> str:
    if name.endswith(".queue"):
        return "queue"
    return _PLANES.get(name) or _PLANES.get(name.split(".", 1)[0], "host")


class Span:
    __slots__ = ("name", "plane", "start_ms", "duration_ms", "outcome")

    def __init__(self, name: str, plane: str, start_ms: float,
                 duration_ms: float, outcome: str) -> None:
        self.name = name
        self.plane = plane
        self.start_ms = start_ms
        self.duration_ms = duration_ms
        self.outcome = outcome

    def to_obj(self) -> dict:
        return {"name": self.name, "plane": self.plane,
                "start_ms": round(self.start_ms, 3),
                "duration_ms": round(self.duration_ms, 3),
                "outcome": self.outcome}


class Trace:
    """One request's identity and, when ``keep`` (the slowest-N buffer
    is armed), its span collection.  ``add`` is thread-safe: loop
    callbacks, hedge tasks AND pipeline worker threads all record onto
    the same trace."""

    __slots__ = ("trace_id", "keep", "t0", "spans", "dropped_spans",
                 "_lock")

    def __init__(self, trace_id: str, keep: bool = True) -> None:
        self.trace_id = trace_id
        self.keep = keep
        self.t0 = _clock.monotonic()
        self.spans: list[Span] = []
        self.dropped_spans = 0
        self._lock = threading.Lock()

    def add(self, name: str, plane: str, start: float, duration: float,
            outcome: str = "ok") -> None:
        """Record one span; ``start`` is a clock-seam ``monotonic()``
        stamp (converted to ms offset from the trace's birth)."""
        if not self.keep:
            return
        span = Span(name, plane, (start - self.t0) * 1000.0,
                    duration * 1000.0, outcome)
        with self._lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped_spans += 1
                return
            self.spans.append(span)

    def to_obj(self, duration_ms: float, meta: dict) -> dict:
        with self._lock:
            spans = [s.to_obj() for s in self.spans]
            dropped = self.dropped_spans
        planes: dict[str, float] = {}
        for s in spans:
            planes[s["plane"]] = planes.get(s["plane"], 0.0) \
                + s["duration_ms"]
        return {"trace_id": self.trace_id,
                "duration_ms": round(duration_ms, 3),
                "plane_ms": {k: round(v, 3)
                             for k, v in sorted(planes.items())},
                "spans": spans,
                **({"dropped_spans": dropped} if dropped else {}),
                **meta}


class TraceBuffer:
    """Bounded slowest-N keeper: a min-heap on duration, so a new slow
    trace evicts the fastest retained one — the buffer converges on the
    worst tail, exactly the requests worth debugging."""

    def __init__(self, capacity: int = BUFFER_CAPACITY) -> None:
        self.capacity = capacity
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._heap: list[tuple[float, int, dict]] = []

    def offer(self, duration_ms: float, record: dict) -> bool:
        with self._lock:
            if len(self._heap) < self.capacity:
                heapq.heappush(self._heap,
                               (duration_ms, next(self._seq), record))
                return True
            if self._heap and duration_ms > self._heap[0][0]:
                heapq.heapreplace(
                    self._heap, (duration_ms, next(self._seq), record))
                return True
            return False

    def snapshot(self) -> list[dict]:
        """Retained traces, slowest first."""
        with self._lock:
            items = sorted(self._heap,
                           key=lambda t: (-t[0], -t[1]))
        return [rec for _d, _s, rec in items]

    def clear(self) -> None:
        with self._lock:
            self._heap = []


#: the process-wide slowest-N buffer /debug/traces serves; per-worker
#: like every other serving-plane store (the fleet view is per-worker
#: by design — a trace is a single worker's story)
_BUFFER = TraceBuffer()


def buffer() -> TraceBuffer:
    return _BUFFER


def mint_id() -> str:
    return os.urandom(8).hex()


def clean_id(raw: Optional[str]) -> str:
    """A usable trace id from a client's ``X-Chunky-Trace`` header —
    minted fresh when absent or unprintable/oversized (header values
    land in JSON debug payloads; garbage must not)."""
    if raw:
        raw = raw.strip()
        if 0 < len(raw) <= _MAX_ID_LEN and raw.isprintable() \
                and not _ID_REJECTS.intersection(raw):
            return raw
    return mint_id()


def start(trace_id: str, keep: bool = True
          ) -> tuple["Trace", "contextvars.Token"]:
    """Open a trace and make it current; pair with :func:`finish`.
    ``keep`` arms the slowest-N sink for it (else it is the request's
    identity for the profiler's trace only)."""
    trace = Trace(trace_id, keep)
    token = _CURRENT.set(trace)
    return trace, token


def finish(trace: "Trace", token: "contextvars.Token", *,
           duration: float, slow_s: float, meta: dict) -> bool:
    """Close out a trace: restore the context and, when it is kept and
    the request ran at least ``slow_s``, file it in the slowest-N
    buffer.  Returns whether the trace was retained."""
    _CURRENT.reset(token)
    duration_ms = duration * 1000.0
    if not trace.keep or duration < slow_s:
        return False
    return _BUFFER.offer(duration_ms,
                         trace.to_obj(duration_ms, dict(meta)))


def current() -> Optional["Trace"]:
    """The context's active trace, or None (untraced — the one-call
    fast path every instrumented site pays)."""
    return _CURRENT.get()


class _Bound:
    __slots__ = ("_trace", "_token")

    def __init__(self, trace: "Trace") -> None:
        self._trace = trace
        self._token = None

    def __enter__(self) -> "_Bound":
        self._token = _CURRENT.set(self._trace)
        return self

    def __exit__(self, *exc) -> bool:
        _CURRENT.reset(self._token)
        return False


def bind(trace: Optional["Trace"]):
    """Make ``trace`` current on this thread for a ``with`` block — a
    pipeline job running on a worker for the request that submitted
    it.  ``None`` leaves the context alone."""
    return _NOOP if trace is None else _Bound(trace)


#: ``jax.profiler.TraceAnnotation`` once jax is imported (resolved on
#: first sight, never imported from here)
_ANNOTATION = None


def profiling() -> bool:
    """Whether a JAX profiler session is recording in this process."""
    global _ANNOTATION
    if _ANNOTATION is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is None:
            return False
        _ANNOTATION = prof.TraceAnnotation
    return _ANNOTATION.is_enabled()


class _NoSpan:
    """The shared span of the off path."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def end(self, outcome: str = "ok", **args) -> None:
        pass


_NOOP = _NoSpan()


class _Span:
    """A live span: the profiler annotation (open since construction)
    and/or the slowest-N record.  ``end`` closes it once; a span may
    close on another thread than the one that opened it (queue waits)."""

    __slots__ = ("name", "_trace", "_start", "_annotation")

    def __init__(self, name: str, trace: Optional["Trace"],
                 profiled: bool, args: dict) -> None:
        self.name = name
        self._trace = trace if trace is not None and trace.keep else None
        self._start = _clock.monotonic() if self._trace is not None \
            else 0.0
        self._annotation = None
        if profiled:
            if trace is not None:
                args["req"] = trace.trace_id
            self._annotation = _ANNOTATION(name, **args)

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end("ok" if exc_type is None else "error")
        return False

    def end(self, outcome: str = "ok", **args) -> None:
        """Close the span; ``args`` known only at the end (a status, a
        byte count) join the annotation's."""
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            if args:
                annotation.set_metadata(**args)
            annotation.__exit__(None, None, None)
        trace, self._trace = self._trace, None
        if trace is not None:
            trace.add(self.name, _plane(self.name), self._start,
                      _clock.monotonic() - self._start, outcome)


def span(name: str, **args):
    """A span of the context's request; see the module docstring."""
    trace = _CURRENT.get()
    profiled = profiling()
    if not profiled and (trace is None or not trace.keep):
        return _NOOP
    return _Span(name, trace, profiled, args)
