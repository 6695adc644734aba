"""Double-buffered async device-dispatch window.

JAX dispatch is asynchronous: calling a jitted function enqueues the
H2D transfer and the kernel and returns a future-like device array;
the host only blocks when it materializes the result (``np.asarray``).
``JaxBackend._pipelined_blocks`` exploits that locally for one
``apply_matrix`` call; this module lifts the same discipline into a
standalone, thread-safe window so a backend can keep it warm ACROSS
calls — block k+1's H2D and the host hash stage run while block k
computes and block k-1 drains D2H (the classic double buffer, depth 2).

The pipeline is deliberately device-agnostic: ``submit`` takes an
``issue`` thunk (non-blocking enqueue — ``device_put`` + jitted call)
and a ``materialize`` function (the blocking D2H wait).  That keeps this
module free of jax imports and unit-testable with plain callables.

Ordering is FIFO: materializations happen oldest-first, so the window
never holds more than ``depth`` un-materialized dispatches and device
memory stays bounded (each in-flight bit-plane dispatch costs ~16x its
byte size).  A failed materialization drops every younger entry, which
then raises :class:`DispatchCancelled` from ``result``.

Overlap is counted, not assumed: ``stats()`` exposes how many submits
found the window busy (``submits_while_busy`` — the feed-ahead events)
and the deepest window (``max_inflight``), plus host seconds spent in
callbacks while dispatches were in flight (``host_overlap_s``, fed by
the mesh backend's block callbacks).  bench --config 17 asserts these
in-run as the platform-independent overlap proof.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Optional

from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.obs import tracing as obs_tracing

#: classic double buffer: one dispatch computing, one staging
DEFAULT_DEPTH = 2

_PENDING, _DONE, _FAILED, _CANCELLED = range(4)


class DispatchCancelled(RuntimeError):
    """Raised by ``result`` for entries dropped because an older entry
    of the same window failed."""


@dataclass
class DispatchStats:
    """Counter snapshot; see module docstring for field semantics."""

    submitted: int = 0
    completed: int = 0
    cancelled: int = 0
    max_inflight: int = 0
    submits_while_busy: int = 0
    host_overlap_s: float = 0.0


class _Entry:
    __slots__ = ("handle", "materialize", "state", "value", "error")

    def __init__(self, materialize: Callable[[object], object]) -> None:
        self.handle: object = None
        self.materialize = materialize
        self.state = _PENDING
        self.value: object = None
        self.error: Optional[BaseException] = None


class DispatchPipeline:
    """Bounded FIFO window of in-flight device dispatches.

    ``depth`` is the number of un-materialized dispatches the window
    may hold after a submit returns: 2 (default) is the double buffer,
    1 keeps a single dispatch in flight, 0 disables overlap entirely
    (every submit materializes synchronously — the bench A/B's "off"
    leg).  ``None`` reads ``tunables.dispatch_depth()``
    ($CHUNKY_BITS_TPU_DISPATCH_DEPTH) at construction.

    Thread-safe via one coarse lock: a materialization holds the lock,
    so concurrent submitters queue behind it — acceptable because the
    device is the serial resource anyway, and required for the FIFO
    memory bound.  NOT loop-bound: batcher worker threads
    (asyncio.to_thread) and sync callers share one instance.
    """

    def __init__(self, depth: Optional[int] = None,
                 name: str = "dispatch") -> None:
        if depth is None:
            from chunky_bits_tpu.cluster.tunables import dispatch_depth

            depth = dispatch_depth(default=DEFAULT_DEPTH)
        self.depth = max(0, int(depth))
        self.name = name
        self._lock = threading.Lock()
        self._window: list[_Entry] = []
        self._stats = DispatchStats()

    def submit(self, issue: Callable[[], object],
               materialize: Callable[[object], object]) -> _Entry:
        """Issue a dispatch and admit it to the window, materializing
        the oldest entries first if the window would exceed ``depth``.
        ``issue`` must be a non-blocking enqueue; its return value is
        the handle later passed to ``materialize``."""
        with self._lock:
            st = self._stats
            st.submitted += 1
            if self._window:
                st.submits_while_busy += 1
            entry = _Entry(materialize)
            entry.handle = issue()
            self._window.append(entry)
            st.max_inflight = max(st.max_inflight, len(self._window))
            while len(self._window) > self.depth:
                self._materialize_oldest_locked()
            return entry

    def result(self, entry: _Entry) -> object:
        """Block until ``entry`` is materialized (draining everything
        older first) and return its value; re-raises a stored
        materialization error, :class:`DispatchCancelled` for dropped
        entries."""
        with self._lock:
            while entry.state == _PENDING:
                self._materialize_oldest_locked()
            if entry.state == _CANCELLED:
                raise DispatchCancelled(
                    f"{self.name}: dispatch cancelled before completion")
            if entry.state == _FAILED:
                raise entry.error  # type: ignore[misc]
            return entry.value

    def drain(self) -> None:
        """Materialize every pending entry (oldest first).  The flush
        used by tests and shutdown paths; errors propagate like
        ``result``'s."""
        with self._lock:
            while self._window:
                self._materialize_oldest_locked()

    @property
    def inflight(self) -> int:
        with self._lock:
            return len(self._window)

    def note_host_overlap(self, seconds: float) -> None:
        """Record host-side staging/callback time spent while at least
        one dispatch was in flight (the bench overlap span)."""
        with self._lock:
            self._stats.host_overlap_s += seconds

    def stats(self) -> DispatchStats:
        with self._lock:
            return DispatchStats(**vars(self._stats))

    def _materialize_oldest_locked(self) -> None:
        e = self._window.pop(0)
        try:
            # the device wait plus the device->host copy (obs/tracing.py)
            with obs_tracing.span("codec.collect") as collect:
                e.value = e.materialize(e.handle)
                nbytes = getattr(e.value, "nbytes", 0)
                collect.end(bytes=nbytes)
            obs_metrics.record_codec_transfer("d2h", nbytes)
            e.state = _DONE
            self._stats.completed += 1
        except BaseException as err:
            # A failed materialization poisons the whole window:
            # younger dispatches sit behind the same failed device.
            # Drop them and surface the error to whoever is driving
            # the drain; their owners see DispatchCancelled.
            e.state = _FAILED
            e.error = err
            for rest in self._window:
                rest.state = _CANCELLED
                rest.handle = None
            self._stats.cancelled += len(self._window)
            self._window.clear()
            raise
