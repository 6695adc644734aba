"""Multi-chip ``mesh`` erasure backend: auto-laid-out sharded dispatch.

``backend: mesh`` (or ``$CHUNKY_BITS_TPU_BACKEND=mesh``) puts the
erasure plane on EVERY visible device with per-dispatch layout
selection, where ``jax:dp4,sp2`` (parallel/backend.py) pins one
explicit mesh for the whole process.  The staged ``[B, d, S]``
encode/decode batches from the batching layer (ops/batching.py) are
sharded per call:

* batch-parallel ``('dp', 'sp')`` by default — the part-batch axis over
  ``dp`` (parts are independent stripes) and, when the batch alone
  cannot fill the mesh, shard bytes over the leftover ``sp`` axis;
* wide-stripe ``('dp', 'tp')`` when the stripe is wide enough that a
  single-stripe matmul saturates one core (``k >=
  WIDE_STRIPE_MIN_K``) and the batch cannot cover the devices: the
  GF contraction axis splits over ``tp`` with an integer psum over ICI
  (parallel/mesh.py, the ``dryrun_multichip`` layout).

The per-chip transform is the existing bit-plane kernel, unchanged,
under ``jit`` + shard_map (``parallel/mesh.py`` — einsum on CPU
meshes, the fused Pallas kernel on TPU chips); on TPU meshes the
staged device buffers are donated back to the allocator
(``donate=True``), never on CPU where XLA may alias host numpy memory.

Dispatch rides the shared :class:`DispatchPipeline`
(ops/dispatch_pipeline.py): block k+1's H2D and the host hash stage
overlap block k's compute and block k-1's D2H, bounded at
``tunables.dispatch_depth()`` in-flight dispatches (default 2, the
double buffer).  ``submit_apply`` exposes the feed-ahead surface the
ingest path uses to stage whole batches ahead of dispatch
(ops/backend.py ``encode_hash_batches``).

XLA CPU quirks stay out of this path by construction (CLAUDE.md
"Environment quirks"): byte-sharded dispatches are padded so every
per-device slice is a multiple of ``LANE`` = 64 bytes, jit bodies are
the existing small kernels (no unrolled loops, no device concats —
blocks concatenate on the host).  Padding is sliced back after
materialization; GF transforms are columnwise, so padding never leaks
into real output and every backend stays byte-identical (conformance
fuzz + golden fixtures pin it).

A device failure (init, compile, dispatch) raises ``ErasureError`` with
the cause; the backend never recomputes on another codec.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from chunky_bits_tpu.errors import ErasureError
from chunky_bits_tpu.obs import tracing as obs_tracing
from chunky_bits_tpu.ops.backend import ErasureBackend
from chunky_bits_tpu.ops.dispatch_pipeline import DispatchPipeline

#: contraction-split threshold: stripes at least this wide take the
#: ('dp', 'tp') wide-stripe layout when the batch alone cannot fill the
#: mesh (BASELINE.md config 5's regime — d=20 saturates one core)
WIDE_STRIPE_MIN_K = 16

#: per-device byte-slice alignment for the 'sp' axis — this jax build's
#: XLA CPU backend misbehaves on odd-width u8 device buffers, and real
#: chips want lane-aligned slices anyway (CLAUDE.md)
LANE = 64


@dataclass(frozen=True)
class Layout:
    """One dispatch's mesh layout: ``('dp', 'tp')`` when ``wide`` else
    ``('dp', 'sp')``; ``minor`` is the tp/sp extent and ``pad_s`` the
    byte padding keeping per-device slices LANE-aligned."""

    wide: bool
    dp: int
    minor: int
    pad_s: int


def _divisors_desc(n: int) -> list[int]:
    return [d for d in range(n, 0, -1) if n % d == 0]


def plan_layout(n: int, b: int, k: int, s: int) -> Layout:
    """Pick the mesh layout for one ``[b, k, s]`` dispatch over ``n``
    devices.  Batch-parallel wants ``dp`` as large as the batch allows
    (parts shard with zero collectives); the leftover axis goes to the
    stripe (``tp``, wide stripes only — one integer psum) or to shard
    bytes (``sp``, element-wise, padded to ``minor * LANE``)."""
    b = max(b, 1)
    dp = next(d for d in _divisors_desc(n) if d <= b)
    minor = n // dp
    if minor == 1:
        return Layout(False, dp, 1, 0)
    if k >= WIDE_STRIPE_MIN_K and k % minor == 0:
        return Layout(True, dp, minor, 0)
    return Layout(False, dp, minor, (-s) % (minor * LANE))


class _MeshTicket:
    """One ``submit_apply`` call's handle: the un-materialized sharded
    dispatches of a ``[B, k, S]`` batch.  ``result()`` drains them
    FIFO through the owning backend's pipeline and fires ``on_block``
    per materialized block."""

    __slots__ = ("_backend", "_entries", "_spans", "_on_block", "_s",
                 "_value", "_done")

    def __init__(self, backend: "MeshBackend", entries: list, spans: list,
                 on_block: Optional[Callable[[int, np.ndarray], None]],
                 s: int, value: Optional[np.ndarray] = None) -> None:
        self._backend = backend
        self._entries = entries
        self._spans = spans
        self._on_block = on_block
        self._s = s
        self._value = value
        self._done = value is not None

    def result(self) -> np.ndarray:
        if self._done:
            return self._value  # type: ignore[return-value]
        be = self._backend
        outs: list[np.ndarray] = []
        for (lo, rows), entry in zip(self._spans, self._entries):
            try:
                arr = be.pipeline.result(entry)
            # lint: broad-except-ok re-raised as ErasureError with the
            # cause chained; nothing is recomputed on another path
            except Exception as err:
                raise ErasureError(
                    f"mesh erasure dispatch failed: {err}") from err
            arr = np.ascontiguousarray(arr[:rows, :, :self._s])
            if self._on_block is not None:
                # lint: clock-escape-ok times REAL host-side work for
                # the overlap-proof counters (bench config 17); real
                # work completes at zero virtual width under sim
                t0 = time.perf_counter()
                self._on_block(lo, arr)
                if be.pipeline.inflight:
                    # lint: clock-escape-ok same real host interval
                    dt = time.perf_counter() - t0
                    be.pipeline.note_host_overlap(dt)
            outs.append(arr)
        out = outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)
        self._value, self._done = out, True
        self._entries = self._spans = None  # type: ignore[assignment]
        return out


class MeshBackend(ErasureBackend):
    """Erasure math sharded over every visible device, fed through a
    bounded double-buffered dispatch window."""

    name = "mesh"

    #: the generic ingest path overlaps host hashing with the sharded
    #: device dispatch (ops/backend.py encode_hash_batch)
    async_dispatch = True

    #: batcher groups route through the feed-ahead submit surface
    #: (ops/batching.py), which supersedes the merged-concat copy
    prefers_merged_batches = True

    #: cap device memory per in-flight dispatch: bits blow bytes up 16x
    #: as bf16 on the einsum impl (same budget as JaxBackend)
    max_block_bytes = 64 << 20

    def __init__(self, depth: Optional[int] = None) -> None:
        import jax

        from chunky_bits_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        devices = jax.devices()
        self.n_devices = len(devices)
        self._on_tpu = devices[0].platform == "tpu"
        self.pipeline = DispatchPipeline(depth=depth, name="mesh dispatch")
        self._meshes: dict[tuple[bool, int, int], object] = {}
        self._mesh_lock = threading.Lock()

    # ---- dispatch plane ----

    @property
    def layouts(self) -> list[tuple[bool, int, int]]:
        """``(wide, dp, minor)`` of every layout dispatched so far."""
        with self._mesh_lock:
            return sorted(self._meshes)

    def _mesh_for(self, lay: Layout):
        key = (lay.wide, lay.dp, lay.minor)
        with self._mesh_lock:
            mesh = self._meshes.get(key)
            if mesh is None:
                from chunky_bits_tpu.parallel import mesh as mesh_mod

                n = lay.dp * lay.minor
                if lay.wide:
                    mesh = mesh_mod.make_stripe_mesh(n, dp=lay.dp,
                                                     tp=lay.minor)
                else:
                    mesh = mesh_mod.make_mesh(n, dp=lay.dp, sp=lay.minor)
                self._meshes[key] = mesh
            return mesh

    def submit_apply(self, mat: np.ndarray, shards: np.ndarray,
                     on_block: Optional[Callable[[int, np.ndarray],
                                                 None]] = None
                     ) -> _MeshTicket:
        """Stage one ``[B, k, S]`` matrix apply into the dispatch
        window and return a ticket; the device starts on it while the
        caller stages more work (the feed-ahead surface
        ``encode_hash_batches`` and the batching layer ride).
        ``on_block(lo, arr)`` fires per materialized block during
        ``result()``, on the collecting thread."""
        from chunky_bits_tpu.parallel import mesh as mesh_mod

        mat = np.ascontiguousarray(mat, dtype=np.uint8)
        shards = np.asarray(shards, dtype=np.uint8)
        b, k, s = shards.shape
        r = mat.shape[0]
        if r == 0 or b == 0 or s == 0:
            out = np.zeros((b, r, s), dtype=np.uint8)
            if on_block is not None and b:
                on_block(0, out)
            return _MeshTicket(self, [], [], None, s, value=out)
        lay = plan_layout(self.n_devices, b, k, s)
        mesh = self._mesh_for(lay)
        apply_fn = (mesh_mod.wide_apply_sharded if lay.wide
                    else mesh_mod.sharded_apply)
        padded = (np.pad(shards, ((0, 0), (0, 0), (0, lay.pad_s)))
                  if lay.pad_s else shards)
        per_item = k * (s + lay.pad_s) * 16
        budget = self.max_block_bytes // max(self.pipeline.depth, 1)
        block = max(lay.dp, budget // max(per_item, 1) // lay.dp * lay.dp)
        donate = self._on_tpu
        entries: list = []
        spans: list[tuple[int, int]] = []
        try:
            for lo in range(0, b, block):
                rows = min(block, b - lo)
                blk = padded[lo:lo + rows]
                pad_b = (-rows) % lay.dp
                if pad_b:
                    blk = np.pad(blk, ((0, pad_b), (0, 0), (0, 0)))
                else:
                    blk = np.ascontiguousarray(blk)
                entries.append(self.pipeline.submit(
                    lambda blk=blk: apply_fn(mesh, mat, blk,
                                             donate=donate),
                    np.asarray))
                spans.append((lo, rows))
        # lint: broad-except-ok re-raised as ErasureError with the cause
        # chained; nothing is recomputed on another path
        except Exception as err:
            raise ErasureError(
                f"mesh erasure dispatch failed: {err}") from err
        return _MeshTicket(self, entries, spans, on_block, s)

    def apply_matrix(self, mat: np.ndarray, shards: np.ndarray,
                     on_block: Optional[Callable[[int, np.ndarray],
                                                 None]] = None
                     ) -> np.ndarray:
        """Sharded dispatch, blocking: stage through the pipeline and
        collect.  Byte-identical to every other backend."""
        return self.submit_apply(mat, shards, on_block=on_block).result()

    # ---- ingest plane ----

    def encode_and_hash(self, mat: np.ndarray, shards: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Overlapped ingest, the jax backend's host-overlap shape
        (ops/jax_backend.py encode_and_hash) on the sharded dispatch:
        the mesh computes parity while the shared HostPipeline hashes
        the data rows, and each parity block is hashed as it lands
        while later blocks are still in flight.  Output is identical
        to the fused native engine's, bit for bit."""
        from chunky_bits_tpu.ops.backend import row_hasher
        from chunky_bits_tpu.parallel.host_pipeline import (
            get_host_pipeline,
            join_jobs,
        )

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        b, k, s = shards.shape
        r = mat.shape[0]
        hash_rows = row_hasher()
        data_digests = np.empty((b, k, 32), dtype=np.uint8)
        parity_digests = np.empty((b, r, 32), dtype=np.uint8)
        if b == 0 or s == 0 or r == 0:
            parity = np.zeros((b, r, s), dtype=np.uint8)
            hash_rows(shards, data_digests)
            hash_rows(parity, parity_digests)
            return parity, np.concatenate(
                [data_digests, parity_digests], axis=1)
        pipe = get_host_pipeline()
        jobs = list(pipe.hash_rows_jobs(shards, data_digests))

        def on_block(lo: int, arr: np.ndarray) -> None:
            # axis-0 slices of the C-contiguous digest array are
            # contiguous, so the hasher can write in place
            jobs.extend(pipe.hash_rows_jobs(
                arr, parity_digests[lo:lo + arr.shape[0]]))

        parity = self.apply_matrix(mat, shards, on_block=on_block)
        with obs_tracing.span("codec.hash_join"):
            join_jobs(jobs)
        return parity, np.concatenate([data_digests, parity_digests],
                                      axis=1)
