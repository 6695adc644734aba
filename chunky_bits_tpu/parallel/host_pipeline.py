"""Shared multi-core host compute pipeline: ingest hashing + encode.

Every host-plane path used to run per-shard SHA-256 and per-stripe
GF(2^8) encode on one thread of the box (the ``native:N`` knob reached
the C++ codec but nothing above it).  This module is the one scheduler
they now share: a bounded, stage-aware executor running host compute on
``min(N, nproc)`` daemon worker threads, where N comes from an explicit
``HostPipeline(threads=N)``, the cluster's ``tunables.host_threads``,
or ``$CHUNKY_BITS_TPU_HOST_THREADS`` (read at first use).  The
memory-pass discipline follows *Accelerating XOR-based Erasure Coding
using Program Optimization Techniques* (arXiv:2108.02692): the round-4
fused encode+hash already touches each byte once per stripe; here that
per-stripe pass is scaled across cores instead of being restructured.

Slicing units (zero-copy by construction):

* **stripes** for the fused native encode+hash: each worker runs the
  cache-hot single pass over a contiguous stripe range, writing straight
  into its rows of the shared ``parity``/``digests`` outputs
  (``NativeBackend.encode_and_hash_into``, internal ``nthreads=1`` so
  total parallelism is the scheduler's worker count, honoring a
  ``native:N`` cap);
* **shard rows** for SHA-256 when stripes can't be sliced (a single
  stripe, or a non-fused backend): data rows hash on the workers while
  the stripe encode — a device dispatch for the jax/mesh backends —
  runs on the calling thread.  This subsumes the round-4 ingest-overlap
  pool (ops/backend.py's retired ``_ingest_hash_pool``).

Ordered completion is positional: every job writes only its own slice of
a preallocated output, so batch results assemble with no reorder step
and the writer's placement semantics (writer.rs:50-59 geometry, the
100 ms stagger chain) are untouched above this layer.

Invariants by construction (CLAUDE.md):

* workers are ``threading.Thread(daemon=True)`` and never required for
  interpreter exit (CB103);
* every queue put/get is bounded: workers poll ``get`` with a timeout
  and re-check shutdown, ``submit`` never blocks (a full queue runs the
  job on the caller — exactly the backpressure wanted), and the async
  path's blocking put is both off-loop and timeout-polled (CB101);
* a job's result-or-error is recorded in a ``finally`` before its
  waiters wake, so no waiter can hang on a completed job.

Byte identity: slicing never changes the math — stripes are independent
in GF(2^8) and SHA-256 is per-row — pinned by tests/test_host_pipeline.py
fuzz across worker counts and backends.
"""

from __future__ import annotations

import asyncio
import os
import queue
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from chunky_bits_tpu.errors import ErasureError
from chunky_bits_tpu.obs import tracing as obs_tracing

#: worker queue poll: the bound on every blocking get/put — short enough
#: that shutdown is prompt, long enough to stay off the scheduler's hot
#: path (a parked worker wakes on the put, not the timeout)
_POLL_SECONDS = 0.5


def _sanitizer():
    """The active runtime concurrency sanitizer, or None.  Reached only
    through ``sys.modules`` so the sanitize-off path costs one dict
    lookup and never imports the instrumentation (the zero-overhead
    contract pinned by tests/test_sanitizer.py)."""
    mod = sys.modules.get("chunky_bits_tpu.analysis.sanitizer")
    return mod.active() if mod is not None else None


class _Job:
    """One unit of host compute: a zero-arg callable tagged with a stage
    name and a byte count for the per-stage counters.  A minimal future:
    the running thread records result-or-exception and fires callbacks
    exactly once; waiters block on the event (sync) or bridge to a loop
    future (async)."""

    __slots__ = ("stage", "fn", "nbytes", "result", "error",
                 "_event", "_callbacks", "_lock", "_started",
                 "trace", "queued")

    def __init__(self, stage: str, fn: Callable[[], Any],
                 nbytes: int = 0) -> None:
        self.stage = stage
        self.fn = fn
        self.nbytes = nbytes
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self._event = threading.Event()
        self._callbacks: list[Callable[["_Job"], None]] = []
        self._lock = threading.Lock()
        self._started = False
        # capture-at-submit: the contextvar trace of the SUBMITTING
        # thread (None when untraced or the submitter is itself a
        # worker) rides the job across the plane boundary so queue-wait
        # and execution spans land on the request that asked
        # (obs/tracing.py — a ContextVar.get and the profiler's
        # enabled-check when no span sink is on)
        self.trace = obs_tracing.current()
        self.queued = obs_tracing.span(f"host.{stage}.queue")

    def _claim(self) -> bool:
        """Atomically claim the right to run this job.  Shutdown races
        hand the same queued job to both a worker/drain and a caller-side
        rescue; exactly one claimant executes ``fn``."""
        with self._lock:
            if self._started:
                return False
            self._started = True
            return True

    def add_done_callback(self, cb: Callable[["_Job"], None]) -> None:
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        cb(self)

    def _finish(self) -> None:
        with self._lock:
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for cb in cbs:
            cb(self)

    def join(self) -> None:
        """Wait for completion without raising.  The poll keeps the wait
        interruptible at interpreter shutdown; jobs always finish — the
        runner records result-or-error in a ``finally``."""
        if not self._event.is_set():
            # a blocking wait issued FROM a loop thread stalls every
            # request on that loop; the sanitizer records it (an
            # already-finished job — the inline small-job path — never
            # waits, so it is exempt by the is_set() guard)
            san = _sanitizer()
            if san is not None:
                san.handoff.check_sync_wait("_Job.join()")
        while not self._event.wait(_POLL_SECONDS):
            pass

    def wait(self) -> Any:
        """Result, re-raising the job's error verbatim."""
        self.join()
        if self.error is not None:
            raise self.error
        return self.result


def join_jobs(jobs: list[_Job]) -> None:
    """Wait for every job, then raise the first recorded error (after
    all finished, so shared output buffers are quiescent when the caller
    unwinds)."""
    for job in jobs:
        job.join()
    for job in jobs:
        if job.error is not None:
            raise job.error


def _ranges(n: int, k: int) -> list[tuple[int, int]]:
    """min(k, n) contiguous near-even [lo, hi) slices covering range(n)."""
    k = max(1, min(k, n))
    base, rem = divmod(n, k)
    out = []
    lo = 0
    for i in range(k):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


@dataclass(frozen=True)
class PipelineStageStats:
    stage: str
    jobs: int
    busy_s: float
    nbytes: int

    def to_obj(self) -> dict:
        return {"stage": self.stage, "jobs": self.jobs,
                "busy_s": round(self.busy_s, 6), "nbytes": self.nbytes}

    def __str__(self) -> str:
        return f"{self.stage}: {self.jobs}j/{self.busy_s:.3f}s/{self.nbytes}B"


@dataclass(frozen=True)
class PipelineStats:
    """Cumulative process counters (like the cache's): saturation is
    observable — per-stage busy seconds and bytes against worker idle
    seconds — not asserted."""

    threads: int
    idle_s: float
    stages: tuple[PipelineStageStats, ...]

    def to_obj(self) -> dict:
        return {"threads": self.threads,
                "idle_s": round(self.idle_s, 6),
                "stages": [s.to_obj() for s in self.stages]}

    def __str__(self) -> str:
        inner = " | ".join(str(s) for s in self.stages)
        if inner:
            inner += " | "
        return f"Pipeline<{self.threads}w {inner}idle {self.idle_s:.3f}s>"


class HostPipeline:
    """Bounded stage-aware scheduler for host compute (see module
    docstring).  ``threads=None`` resolves ``tunables.host_threads`` and
    clamps to ``min(N, nproc)``; an explicit count is honored exactly so
    scaling sweeps and tests can pin or oversubscribe deliberately.

    The sync entry points (``submit``/``encode_hash_sync``) are for
    worker/ordinary threads; the async ones (``run``/``encode_hash``)
    are loop-safe and never block the event loop.
    """

    #: async jobs at or below this byte count run inline on the awaiting
    #: coroutine instead of hopping to a worker: the hop latency exceeds
    #: the compute (BASELINE round 5 measured the same effect fusing the
    #: page-cache map with hash verification), and lockstep completion
    #: preserves the arrival clustering the downstream reconstruct/encode
    #: batchers coalesce on.  0-byte (unknown-size) jobs always offload.
    INLINE_NBYTES = 128 << 10

    def __init__(self, threads: Optional[int] = None, *,
                 queue_depth: Optional[int] = None,
                 name: str = "cb-host") -> None:
        nproc = os.cpu_count() or 1
        if threads is None:
            from chunky_bits_tpu.cluster.tunables import host_threads

            n = min(host_threads(default=0) or nproc, nproc)
        else:
            n = int(threads)
        self.threads = max(1, n)
        self._q: "queue.Queue[_Job]" = queue.Queue(
            maxsize=queue_depth or max(128, 8 * self.threads))
        self._shutdown = threading.Event()
        self._lock = threading.Lock()
        self._stages: dict[str, list] = {}  # stage -> [jobs, busy_s, bytes]
        self._idle_s = 0.0
        self._local = threading.local()
        # self-activate the runtime sanitizer when the flag asks for it
        # (read-at-first-use like host_threads); when off, nothing is
        # imported and no per-job instrumentation exists
        from chunky_bits_tpu.cluster.tunables import sanitize_enabled

        if sanitize_enabled():
            from chunky_bits_tpu.analysis.sanitizer import get_monitor

            get_monitor()
        # weakly self-register with the process metrics registry so a
        # /metrics scrape folds in per-stage busy/idle/bytes counters
        # (stats() is already lock-guarded and thread-safe)
        from chunky_bits_tpu.obs.metrics import get_registry

        get_registry().register_source("pipeline", self)
        self._workers = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"{name}-{i}")
            for i in range(self.threads)
        ]
        for w in self._workers:
            w.start()

    # ---- worker plumbing ----

    def _worker(self) -> None:
        self._local.on_worker = True
        while not self._shutdown.is_set():
            t0 = time.perf_counter()
            try:
                job = self._q.get(timeout=_POLL_SECONDS)
            except queue.Empty:
                continue
            dt = time.perf_counter() - t0
            with self._lock:
                self._idle_s += dt
            self._run_job(job)

    def _run_job(self, job: _Job) -> None:
        if not job._claim():
            return  # a racing claimant (shutdown rescue) already ran it
        # two spans per job: how long it WAITED (the queue —
        # saturation's signature) and how long it RAN, both on the
        # request that submitted it (bound here, on the worker, so spans
        # inside the job carry it too)
        job.queued.end()
        # lint: clock-escape-ok real worker-thread stage profiling;
        # real thread work has zero virtual width under sim
        t0 = time.perf_counter()
        with obs_tracing.bind(job.trace), \
                obs_tracing.span(f"host.{job.stage}",
                                 bytes=job.nbytes) as run:
            try:
                job.result = job.fn()
            # lint: broad-except-ok delivered verbatim to the waiter via
            # job.error (wait/join_jobs re-raise); nothing is swallowed
            except BaseException as err:
                job.error = err
            finally:
                # lint: clock-escape-ok real worker-thread stage profiling
                dt = time.perf_counter() - t0
                with self._lock:
                    st = self._stages.setdefault(job.stage, [0, 0.0, 0])
                    st[0] += 1
                    st[1] += dt
                    st[2] += job.nbytes
                run.end("ok" if job.error is None else "error")
                job._finish()

    def _offer(self, job: _Job) -> None:
        """Queue a job without ever blocking: a full queue, shutdown, or
        a call from one of our own workers runs it inline on the caller
        (backpressure lands on the producer; worker reentrancy can never
        deadlock on queue capacity)."""
        if getattr(self._local, "on_worker", False) \
                or self._shutdown.is_set():
            self._run_job(job)
            return
        try:
            self._q.put_nowait(job)
        except queue.Full:
            self._run_job(job)
            return
        if self._shutdown.is_set():
            # closed between the check and the put: the queue may never
            # be serviced again — rescue inline (the claim makes this a
            # no-op if a worker or close()'s drain got there first)
            self._run_job(job)

    def _put_blocking(self, job: _Job) -> None:
        """Off-loop blocking put, timeout-polled against shutdown; the
        post-put shutdown re-check rescues a job stranded by a racing
        close() (claimed exactly once — see ``_Job._claim``)."""
        while not self._shutdown.is_set():
            try:
                self._q.put(job, timeout=_POLL_SECONDS)
            except queue.Full:
                continue
            if self._shutdown.is_set():
                self._run_job(job)
            return
        self._run_job(job)

    # ---- core API ----

    def submit(self, stage: str, fn: Callable[[], Any], *,
               nbytes: int = 0) -> _Job:
        """Queue one job (sync callers); returns its handle for
        ``wait()``.  Never blocks — see ``_offer``."""
        job = _Job(stage, fn, nbytes)
        self._offer(job)
        return job

    async def run(self, stage: str, fn: Callable[[], Any], *,
                  nbytes: int = 0) -> Any:
        """Run one sync job on the pipeline and await its result — the
        ``asyncio.to_thread`` analogue with stage accounting and the
        bounded shared worker set.  Small known-size jobs run inline
        (see ``INLINE_NBYTES``)."""
        job = _Job(stage, fn, nbytes)
        if 0 < nbytes <= self.INLINE_NBYTES:
            self._run_job(job)
            return job.wait()
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        san = _sanitizer()
        token = san.handoff.submit_token() if san is not None else None

        def bridge(j: _Job) -> None:
            def resolve() -> None:
                if token is not None and san is not None:
                    # the handoff contract: this completion must be
                    # delivered on the submitting loop's thread
                    san.handoff.check_resolve(token)
                if fut.cancelled():
                    return
                if j.error is not None:
                    fut.set_exception(j.error)
                else:
                    fut.set_result(j.result)

            try:
                loop.call_soon_threadsafe(resolve)
            except RuntimeError:
                pass  # loop already closed; no waiter left to wake

        job.add_done_callback(bridge)
        if self._shutdown.is_set():
            # closed pipeline: degrade to a plain thread hop, never hang
            # (stragglers on a cluster whose pinned pipeline a sweep just
            # closed still complete)
            await asyncio.to_thread(self._run_job, job)
        else:
            try:
                self._q.put_nowait(job)
            except queue.Full:
                await asyncio.to_thread(self._put_blocking, job)
            else:
                if self._shutdown.is_set():
                    # close() raced the put: rescue off-loop (no-op if a
                    # worker or the close drain claimed the job first)
                    await asyncio.to_thread(self._run_job, job)
        # lint: unbounded-await-ok resolved in every outcome: the runner
        # records result-or-error in a finally and fires the bridge
        # callback; jobs are pure host compute on daemon workers (no
        # PJRT park on this path)
        return await fut

    def _scatter(self, jobs: list[_Job]) -> None:
        """Fan jobs out to the workers and wait.  Every job goes through
        the queue — never the calling thread — so concurrent scatters
        (e.g. the writer's double-buffered sub-blocks) share exactly the
        scheduler's N workers instead of stacking extra caller threads on
        top: the thread-count knob stays honest.  Deadlock-free at any
        worker count: a call *from* a worker runs inline (``_offer``),
        and a full queue falls back to the caller.  Raises the first job
        error once every job finished (shared outputs quiescent)."""
        for job in jobs:
            self._offer(job)
        join_jobs(jobs)

    # ---- the ingest compute: sliced encode + hash ----

    def hash_rows_jobs(self, rows: np.ndarray, out: np.ndarray, *,
                       stage: str = "hash") -> list[_Job]:
        """Queue sliced row-hash jobs — ``out[..., 32] = sha256`` of each
        ``rows[..., S]`` row — WITHOUT waiting (callers overlap them with
        an in-flight device dispatch, then ``join_jobs``).  Both arrays
        must be C-contiguous: each slice writes through a flat view."""
        if not (rows.flags.c_contiguous and out.flags.c_contiguous):
            raise ErasureError("hash_rows_jobs needs contiguous arrays")
        flat = rows.reshape(-1, rows.shape[-1]) if rows.ndim != 2 else rows
        oflat = out.reshape(-1, 32) if out.ndim != 2 else out
        hasher = _row_hasher()
        jobs = []
        for lo, hi in _ranges(flat.shape[0], self.threads):
            jobs.append(_Job(
                stage,
                lambda lo=lo, hi=hi: hasher(flat[lo:hi], oflat[lo:hi], 1),
                (hi - lo) * flat.shape[-1]))
        for job in jobs:
            self._offer(job)
        return jobs

    def encode_hash_sync(self, coder: Any, stacked: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray]:
        """``ErasureCoder.encode_hash_batch`` scaled across the workers:
        ``(parity[B, p, S], digests[B, d+p, 32])`` for ``stacked[B, d,
        S]``, byte-identical to the single-threaded path at every worker
        count.  Blocking — call from a worker thread (``encode_hash`` is
        the loop-safe wrapper).

        Slicing: stripes for a fused backend (native) with B >= 2; shard
        rows otherwise, with the stripe encode (a device dispatch for
        async backends) on the calling thread while data rows hash on
        the workers.  A ``native:N`` backend caps total parallelism at N
        — the cluster.yaml thread knob keeps meaning *total host
        threads*, not threads-per-worker.
        """
        stacked = np.ascontiguousarray(stacked, dtype=np.uint8)
        if stacked.ndim != 3 or stacked.shape[1] != coder.data:
            raise ErasureError(
                f"expected stacked [B, {coder.data}, S], "
                f"got {stacked.shape}")
        b, d, s = stacked.shape
        p = coder.parity
        if b == 0 or s == 0:
            # degenerate shapes: the coder's own handling (sha256(b"")
            # digests etc.) is already exact and instant
            return coder.encode_hash_batch(stacked)
        cap = getattr(coder.backend, "nthreads", 0) or 0
        k = self.threads if cap <= 0 else min(self.threads, cap)
        fused_into = getattr(coder.backend, "encode_and_hash_into", None)
        fused_whole = getattr(coder.backend, "encode_and_hash", None)
        if not getattr(coder, "supports_fused_ingest", True):
            # sub-symbol codes (pm-msr): the backend's fused passes
            # apply parity_rows at chunk granularity — wrong matrix
            # shape for a stripe-structured code.  Null BOTH so such
            # coders take the decomposed path below: it calls the
            # coder's own encode_batch (exact) with per-shard hashing
            # sliced across the workers, overlapping device dispatch
            # the same way — never a single-threaded whole-batch job
            fused_into = fused_whole = None

        if fused_into is None and fused_whole is not None:
            # a device backend with its own fused/overlapped ingest path
            # (jax: device parity + per-block host hashing — which
            # already rides this pipeline's workers internally): the
            # device does the slicing, so delegate whole and run the
            # host-side orchestration on the calling thread
            job = _Job("encode", lambda: coder.encode_hash_batch(stacked),
                       b * d * s)
            self._run_job(job)
            return job.wait()

        if fused_into is not None and (b >= 2 or k == 1):
            # per-stripe fused pass, k-way sliced, zero-copy outputs
            parity = np.empty((b, p, s), dtype=np.uint8)
            digests = np.empty((b, d + p, 32), dtype=np.uint8)
            jobs = [
                _Job("encode",
                     lambda lo=lo, hi=hi: fused_into(
                         coder.parity_rows, stacked[lo:hi],
                         parity[lo:hi], digests[lo:hi], 1),
                     (hi - lo) * d * s)
                for lo, hi in _ranges(b, k)
            ]
            self._scatter(jobs)
            return parity, digests

        # decomposed path: per-shard SHA sliced across the workers,
        # per-stripe encode either on the calling thread (async-dispatch
        # device backends: a device wait, not host compute — the round-4
        # ingest overlap on shared workers) or queued like any other
        # host job so the worker count stays the ceiling
        hasher = _row_hasher()
        flat = stacked.reshape(b * d, s)
        ddig = np.empty((b * d, 32), dtype=np.uint8)
        hash_jobs = [
            _Job("hash",
                 lambda lo=lo, hi=hi: hasher(flat[lo:hi], ddig[lo:hi], 1),
                 (hi - lo) * s)
            for lo, hi in _ranges(b * d, k)
        ]
        enc = _Job("encode", lambda: coder.encode_batch(stacked), b * d * s)
        if getattr(coder.backend, "async_dispatch", False):
            for job in hash_jobs:
                self._offer(job)
            self._run_job(enc)
            join_jobs(hash_jobs + [enc])
        else:
            self._scatter(hash_jobs + [enc])
        parity = np.ascontiguousarray(enc.result)
        data_digests = ddig.reshape(b, d, 32)
        if p == 0:
            return parity, data_digests
        pdig = np.empty((b * p, 32), dtype=np.uint8)
        pflat = parity.reshape(b * p, s)
        pjobs = [
            _Job("hash",
                 lambda lo=lo, hi=hi: hasher(pflat[lo:hi], pdig[lo:hi], 1),
                 (hi - lo) * s)
            for lo, hi in _ranges(b * p, k)
        ]
        self._scatter(pjobs)
        return parity, np.concatenate(
            [data_digests, pdig.reshape(b, p, 32)], axis=1)

    async def encode_hash(self, coder: Any, stacked: np.ndarray
                          ) -> tuple[np.ndarray, np.ndarray]:
        """Loop-safe ``encode_hash_sync``: the orchestrating hop runs the
        first slice itself (caller-runs-first), so it is working, not
        waiting, and W=1 degrades to exactly one busy thread."""
        return await asyncio.to_thread(self.encode_hash_sync, coder,
                                       stacked)

    # ---- observability / lifecycle ----

    def stats(self) -> PipelineStats:
        with self._lock:
            stages = tuple(
                PipelineStageStats(stage, st[0], st[1], st[2])
                for stage, st in sorted(self._stages.items()))
            return PipelineStats(self.threads, self._idle_s, stages)

    def close(self, timeout: float = 5.0) -> None:
        """Stop the workers (scaling sweeps and tests; the process-shared
        pipeline lives for the process — its workers are daemon and cost
        nothing idle).  Already-queued jobs are drained inline so no
        waiter is abandoned."""
        self._shutdown.set()
        # lint: clock-escape-ok join deadline bounds REAL threads at
        # shutdown — virtual time cannot advance a parked OS thread
        deadline = time.monotonic() + timeout
        for w in self._workers:
            # lint: clock-escape-ok same real join deadline
            w.join(max(0.0, deadline - time.monotonic()))
        while True:
            try:
                job = self._q.get_nowait()
            except queue.Empty:
                break
            self._run_job(job)


def _row_hasher() -> Callable[[np.ndarray, np.ndarray, int], None]:
    from chunky_bits_tpu.ops.backend import row_hasher

    return row_hasher()


_SHARED: Optional[HostPipeline] = None
_SHARED_LOCK = threading.Lock()


def get_host_pipeline() -> HostPipeline:
    """The process-shared pipeline, built on first use with
    ``min($CHUNKY_BITS_TPU_HOST_THREADS or nproc, nproc)`` workers.
    Read-at-first-dispatch (CLAUDE.md): set the env var before the first
    encode/verify — the worker count is baked in for the process."""
    global _SHARED
    if _SHARED is None:
        with _SHARED_LOCK:
            if _SHARED is None:
                _SHARED = HostPipeline()
    return _SHARED
