"""The ``ErasureBackend`` boundary and the per-geometry ``ErasureCoder``.

This is the pluggable seam the north-star asks for, placed at exactly the
boundary the reference has between its part codec and the
``reed-solomon-erasure`` crate (reference: src/file/file_part.rs:77,128,
161-165,302-305 — ``ReedSolomon::new(d, p)`` / ``encode_sep`` /
``reconstruct`` / ``reconstruct_data``).

A backend implements one primitive — apply a GF(2^8) matrix to a batch of
stacked shards — and the coder builds the encode/decode matrices on the host
(they are tiny) and dispatches batches to it.  Backends:

* ``numpy``  — pure-numpy table codec; always available; slow-ish.
* ``native`` — C++ table codec via ctypes (ops/cpu_backend.py); the CPU
  oracle, byte-identical to the reference's crate.
* ``jax``    — batched bit-plane matmuls on TPU (ops/jax_backend.py).
* ``mesh``   — the same bit-plane kernels sharded over every visible
  device with per-dispatch layout selection and a double-buffered
  dispatch window (ops/mesh_backend.py); ``jax:dp4,sp2`` pins one
  explicit mesh instead (parallel/backend.py).

All of them produce byte-identical shards; tests assert it.
"""

from __future__ import annotations

import hashlib
import threading
from abc import ABC, abstractmethod
from typing import Callable, Optional, Sequence

import numpy as np

from chunky_bits_tpu.errors import ErasureError
from chunky_bits_tpu.obs import tracing as obs_tracing
from chunky_bits_tpu.ops import gf256, matrix


class ErasureBackend(ABC):
    """Applies GF(2^8) matrices to batches of shards."""

    name: str = "abstract"

    @abstractmethod
    def apply_matrix(self, mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """out[b, i, s] = XOR_k mat[i, k] ⊗ shards[b, k, s].

        ``mat`` is uint8 [r, k]; ``shards`` is uint8 [B, k, S]; returns
        uint8 [B, r, S].
        """


class NumpyBackend(ErasureBackend):
    """Vectorized table-lookup codec; the always-available fallback."""

    name = "numpy"

    def apply_matrix(self, mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
        b, k, s = shards.shape
        r = mat.shape[0]
        out = np.zeros((b, r, s), dtype=np.uint8)
        for i in range(r):
            acc = out[:, i, :]
            for j in range(k):
                c = int(mat[i, j])
                if c == 0:
                    continue
                if c == 1:
                    acc ^= shards[:, j, :]
                else:
                    acc ^= gf256.gf_mul_bytes(c, shards[:, j, :])
        return out


_REGISTRY: dict[str, ErasureBackend] = {}
_REGISTRY_LOCK = threading.Lock()


def register_backend(backend: ErasureBackend) -> None:
    with _REGISTRY_LOCK:
        _REGISTRY[backend.name] = backend


def _build_device_backend(build: Callable[[], ErasureBackend],
                          what: str) -> ErasureBackend:
    """Construct a device backend; a failed device init (no usable
    platform, a chip another process holds) raises ErasureError with
    the cause — it never turns into a CPU codec."""
    try:
        return build()
    except ErasureError:
        raise
    except Exception as err:  # e.g. no usable jax device/platform
        raise ErasureError(f"{what} unavailable: {err}") from err


def get_backend(name: Optional[str] = None) -> ErasureBackend:
    """Resolve a backend by name, building it lazily.

    ``None`` resolves the default: $CHUNKY_BITS_TPU_BACKEND if set, else the
    native C++ oracle if it builds, else numpy.  The ``jax`` backend is only
    picked by explicit request (cluster tunables or env) because importing
    jax in short-lived CLI calls costs seconds.
    """
    if name is None:
        from chunky_bits_tpu.cluster.tunables import BACKEND_ENV, env_str

        name = env_str(BACKEND_ENV) or "auto"
    with _REGISTRY_LOCK:
        if name in _REGISTRY:
            return _REGISTRY[name]
    if name == "numpy":
        backend: ErasureBackend = NumpyBackend()
    elif name == "native":
        from chunky_bits_tpu.ops.cpu_backend import NativeBackend

        backend = NativeBackend()
    elif name.startswith("native:"):
        # explicit host thread count, e.g. "native:4" — bounds the C++
        # codec/hasher's std::thread fan-out (plain "native" uses
        # hardware_concurrency); the knob cluster.yaml tunables expose
        # for hosts shared with other work
        from chunky_bits_tpu.ops.cpu_backend import NativeBackend

        spec = name[len("native:"):]
        if not spec.isdigit() or int(spec) < 1:
            raise ErasureError(
                f"bad native thread count {spec!r} (want e.g. native:4)")
        backend = NativeBackend(nthreads=int(spec))
        backend.name = name
    elif name == "jax":
        from chunky_bits_tpu.ops.jax_backend import JaxBackend

        backend = _build_device_backend(JaxBackend, "jax erasure backend")
    elif name == "mesh":
        # every visible device, per-dispatch auto layout, double-buffered
        # dispatch window (ops/mesh_backend.py)
        from chunky_bits_tpu.ops.mesh_backend import MeshBackend

        backend = _build_device_backend(MeshBackend,
                                        "mesh erasure backend")
    elif name.startswith("jax:"):
        # mesh-sharded device backend, e.g. "jax:dp4,sp2" / "jax:tp4"
        # (parallel/backend.py)
        from chunky_bits_tpu.parallel.backend import MeshJaxBackend

        backend = _build_device_backend(
            lambda: MeshJaxBackend(name[len("jax:"):]),
            f"mesh jax backend {name!r}")
        # Register the canonical resolved name AND the requested spelling
        # so repeat lookups under either hit the cache.
        register_backend(backend)
        if backend.name != name:
            with _REGISTRY_LOCK:
                _REGISTRY[name] = backend
        return backend
    elif name == "auto":
        try:
            from chunky_bits_tpu.ops.cpu_backend import NativeBackend

            backend = NativeBackend()
        # lint: broad-except-ok native build probe; numpy fallback is
        # byte-identical, only slower
        except Exception:
            backend = NumpyBackend()
        with _REGISTRY_LOCK:
            _REGISTRY["auto"] = backend
            _REGISTRY.setdefault(backend.name, backend)
        return backend
    else:
        raise ErasureError(f"unknown erasure backend {name!r}")
    register_backend(backend)
    return backend


def _hash_rows_hashlib(rows: np.ndarray, out: np.ndarray,
                       nthreads: int = 0) -> None:
    """out[..., 32] = sha256 of each row of uint8 rows[..., S].
    ``nthreads`` is accepted for signature parity with the native engine
    and ignored — hashlib runs row-at-a-time under the GIL here; callers
    wanting parallelism slice rows across the host pipeline's workers."""
    for idx in np.ndindex(rows.shape[:-1]):
        out[idx] = np.frombuffer(
            hashlib.sha256(np.ascontiguousarray(rows[idx])).digest(),
            dtype=np.uint8)


_ROW_HASHER = None


def row_hasher() -> Callable[..., None]:
    """Bulk shard-row hasher ``fn(rows[..., S], out[..., 32],
    nthreads=0)``: the native SHA-NI engine when it builds (GIL-free;
    ``nthreads`` caps its internal fan-out — the host pipeline passes 1
    per slice), else a hashlib loop computing identical digests."""
    global _ROW_HASHER
    if _ROW_HASHER is None:
        try:
            from chunky_bits_tpu.ops.cpu_backend import (sha256_buf,
                                                         sha256_rows)

            sha256_buf(b"")  # force the deferred C++ build now
            _ROW_HASHER = sha256_rows
        # lint: broad-except-ok native build probe; the hashlib loop
        # computes the identical digests, only slower
        except Exception:
            _ROW_HASHER = _hash_rows_hashlib
    return _ROW_HASHER


_CODER_CACHE: dict[tuple[int, int, str, str], "ErasureCoder"] = {}
_CODER_LOCK = threading.Lock()

#: the closed set of erasure codes a part may declare (file/chunk.py
#: ``code:`` field): classic Reed-Solomon and the product-matrix MSR
#: regenerating code (ops/pm_msr.py).  Anything else is a
#: newer/foreign writer — readers degrade to a clean error, never a
#: guess (a non-member code could be non-systematic, so even a
#: fully-healthy read must refuse rather than concatenate data chunks)
KNOWN_CODES = ("rs", "pm-msr")


class ErasureCoder:
    """Reed-Solomon codec for one (d, p) geometry — the ``ReedSolomon::new``
    equivalent (reference: src/file/file_part.rs:77).

    Batched variants take uint8 arrays shaped [B, shards, S]; the scalar
    variants mirror the crate's per-part API and are thin wrappers.
    """

    #: wire-format code name (file/chunk.py ``code:`` field); the
    #: product-matrix MSR subclass (ops/pm_msr.py) overrides
    code = "rs"
    #: whether the host pipeline's chunk-granular fused native ingest
    #: (parity_rows applied to [B, d, S] + per-stripe SHA in one pass)
    #: is valid for this code; sub-symbol codes take the decomposed path
    supports_fused_ingest = True

    def __init__(self, data: int, parity: int,
                 backend: Optional[ErasureBackend] = None) -> None:
        if data < 1:
            raise ErasureError("data shard count must be >= 1")
        if parity < 0:
            raise ErasureError("parity shard count must be >= 0")
        self.data = data
        self.parity = parity
        self.backend = backend or get_backend()
        self.encode_matrix = matrix.build_encode_matrix(data, parity)
        self.parity_rows = self.encode_matrix[data:]

    def shard_len(self, length: int) -> int:
        """Bytes per shard for a part holding ``length`` meaningful
        bytes — the reference's round-up split
        (src/file/file_part.rs:150-158).  Sub-symbol codes round up
        further so every chunk divides into equal stripes."""
        return (length + self.data - 1) // self.data if length > 0 else 0

    # ---- batched API (the TPU-friendly surface) ----

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """parity[B, p, S] from data[B, d, S] (crate: ``encode_sep``)."""
        if data.ndim != 3 or data.shape[1] != self.data:
            raise ErasureError(
                f"expected data shaped [B, {self.data}, S], got {data.shape}"
            )
        if self.parity == 0:
            b, _, s = data.shape
            return np.zeros((b, 0, s), dtype=np.uint8)
        return self.backend.apply_matrix(self.parity_rows, data)

    def encode_hash_batch(
        self, data: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Parity plus per-shard content hashes for a batch of parts —
        the ingest step's full compute (reference: encode at
        src/file/file_part.rs:161-165, per-shard sha256 at :185).

        Returns ``(parity[B, p, S], digests[B, d+p, 32])`` with digest
        rows ordered data shards then parity shards.  Backends exposing a
        fused ``encode_and_hash`` (the native C++ engine) do both in one
        cache-hot pass; otherwise parity comes from ``encode_batch`` and
        hashing falls back to hashlib.
        """
        if data.ndim != 3 or data.shape[1] != self.data:
            raise ErasureError(
                f"expected data shaped [B, {self.data}, S], got {data.shape}"
            )
        fused = (getattr(self.backend, "encode_and_hash", None)
                 if self.supports_fused_ingest else None)
        if fused is not None:
            return fused(self.parity_rows, np.ascontiguousarray(data))
        data = np.ascontiguousarray(data)
        b, _, _ = data.shape
        hash_rows = row_hasher()
        data_digests = np.empty((b, self.data, 32), dtype=np.uint8)
        if getattr(self.backend, "async_dispatch", False):
            # device backends (mesh): hash the data rows on the shared
            # host pipeline's daemon workers (sliced across them) while
            # the device computes parity — the same overlap the retired
            # 2-worker ThreadPoolExecutor provided, now on the bounded
            # CB103-clean executor every host path shares
            from chunky_bits_tpu.parallel.host_pipeline import (
                get_host_pipeline,
                join_jobs,
            )

            jobs = get_host_pipeline().hash_rows_jobs(data, data_digests)
            parity = self.encode_batch(data)
            with obs_tracing.span("codec.hash_join"):
                join_jobs(jobs)
        else:
            parity = self.encode_batch(data)
            hash_rows(data, data_digests)
        if not self.parity:
            return parity, data_digests
        parity = np.ascontiguousarray(parity)
        parity_digests = np.empty((b, self.parity, 32), dtype=np.uint8)
        hash_rows(parity, parity_digests)
        return parity, np.concatenate([data_digests, parity_digests], axis=1)

    def encode_hash_batches(
        self, batches: Sequence[np.ndarray]
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Feed-ahead ingest for several same-geometry batches: one
        ``(parity, digests)`` pair per input batch, byte-identical to
        calling ``encode_hash_batch`` per batch.

        On backends exposing a ``submit_apply`` staging surface (the
        ``mesh`` backend's dispatch pipeline), EVERY batch's dispatch is
        staged before any is collected, so batch k+1's H2D and the host
        hash stage run while batch k computes — the batching layer
        (ops/batching.py) routes merged groups here instead of paying
        the concatenate-and-slice copy.  Other backends just loop.
        """
        submit = getattr(self.backend, "submit_apply", None)
        if (submit is None or not self.parity
                or type(self).encode_batch is not ErasureCoder.encode_batch):
            # no staging surface, nothing to overlap (p=0), or a
            # subclass with its own encode math (pm-msr decomposes into
            # sub-symbol applies — those pipeline at block level inside
            # apply_matrix instead)
            return [self.encode_hash_batch(b) for b in batches]
        from chunky_bits_tpu.parallel.host_pipeline import (
            get_host_pipeline,
            join_jobs,
        )

        pipe = get_host_pipeline()
        hash_rows = row_hasher()
        staged = []
        for data in batches:
            if data.ndim != 3 or data.shape[1] != self.data:
                raise ErasureError(
                    f"expected data shaped [B, {self.data}, S], "
                    f"got {data.shape}")
            data = np.ascontiguousarray(data)
            b = data.shape[0]
            data_digests = np.empty((b, self.data, 32), dtype=np.uint8)
            parity_digests = np.empty((b, self.parity, 32), dtype=np.uint8)
            jobs = list(pipe.hash_rows_jobs(data, data_digests))
            covered = np.zeros(b, dtype=bool)

            def on_block(lo, arr, jobs=jobs, covered=covered,
                         pd=parity_digests):
                covered[lo:lo + arr.shape[0]] = True
                jobs.extend(pipe.hash_rows_jobs(
                    arr, pd[lo:lo + arr.shape[0]]))

            ticket = submit(self.parity_rows, data, on_block=on_block)
            staged.append((ticket, jobs, covered, data_digests,
                           parity_digests))
        out = []
        for ticket, jobs, covered, data_digests, parity_digests in staged:
            parity = ticket.result()
            with obs_tracing.span("codec.hash_join"):
                join_jobs(jobs)
            if not covered.all():
                # rows the callback never saw (mid-run degrade's CPU
                # recompute) hash from the parity actually returned
                idx = np.flatnonzero(~covered)
                rest = np.empty((len(idx), self.parity, 32),
                                dtype=np.uint8)
                hash_rows(np.ascontiguousarray(parity[idx]), rest)
                parity_digests[idx] = rest
            out.append((parity, np.concatenate(
                [data_digests, parity_digests], axis=1)))
        return out

    def reconstruct_batch(
        self, shards: np.ndarray, present: Sequence[int],
        wanted: Sequence[int],
    ) -> np.ndarray:
        """Rebuild ``wanted`` shard rows for a batch sharing one erasure
        pattern.  ``shards[B, d+p, S]`` need only be valid at ``present``
        rows.  Returns [B, len(wanted), S].
        """
        present = sorted(present)
        picked = shards[:, np.array(present[: self.data], dtype=np.intp), :]
        return self.reconstruct_batch_picked(picked, present, wanted)

    def reconstruct_batch_picked(
        self, picked: np.ndarray, present: Sequence[int],
        wanted: Sequence[int],
    ) -> np.ndarray:
        """Like ``reconstruct_batch`` but over shards already gathered in
        decode layout: ``picked[B, d, S]`` holds the rows at
        ``sorted(present)[:d]``, in that order.  Callers that assemble
        the batch themselves (ops/batching.py) stack straight into this
        layout, skipping the full [B, d+p, S] scatter plus the row-pick
        copy that reconstruct_batch would redo."""
        present = sorted(present)
        dec = matrix.decode_matrix(self.encode_matrix, list(present),
                                   list(wanted))
        return self.backend.apply_matrix(dec, picked)

    # ---- per-part API mirroring the crate ----

    def encode(self, data_shards: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Parity shards for one part's data shards (equal-length rows)."""
        rows = [np.frombuffer(s, dtype=np.uint8)
                if not isinstance(s, np.ndarray) else s
                for s in data_shards]
        if len({len(r) for r in rows}) > 1:
            raise ErasureError("shards must be of equal length")
        stacked = np.stack(rows)[None, ...]
        return list(self.encode_batch(stacked)[0])

    def _reconstruct_impl(
        self, shards: list[Optional[np.ndarray]], data_only: bool
    ) -> list[Optional[np.ndarray]]:
        total = self.data + self.parity
        if len(shards) != total:
            raise ErasureError(
                f"expected {total} shard slots, got {len(shards)}"
            )
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) == total:
            return shards
        if len(present) < self.data:
            raise ErasureError(
                f"too few shards present: {len(present)} < {self.data}"
            )
        limit = self.data if data_only else total
        missing = [i for i in range(limit) if shards[i] is None]
        if not missing:
            return shards
        size = len(shards[present[0]])
        stacked = np.zeros((1, total, size), dtype=np.uint8)
        for i in present:
            row = shards[i]
            if not isinstance(row, np.ndarray):
                row = np.frombuffer(row, dtype=np.uint8)
            if len(row) != size:
                raise ErasureError("shards must be of equal length")
            stacked[0, i] = row
        rebuilt = self.reconstruct_batch(stacked, present, missing)[0]
        out = list(shards)
        for row, idx in zip(rebuilt, missing):
            out[idx] = row
        return out

    def reconstruct(
        self, shards: list[Optional[np.ndarray]]
    ) -> list[Optional[np.ndarray]]:
        """Fill every missing shard (crate: ``reconstruct``,
        reference call site src/file/file_part.rs:302-305)."""
        return self._reconstruct_impl(shards, data_only=False)

    def reconstruct_data(
        self, shards: list[Optional[np.ndarray]]
    ) -> list[Optional[np.ndarray]]:
        """Fill missing *data* shards only (crate: ``reconstruct_data``,
        reference call site src/file/file_part.rs:128)."""
        return self._reconstruct_impl(shards, data_only=True)


def get_coder(data: int, parity: int,
              backend: Optional[str] = None,
              code: str = "rs") -> ErasureCoder:
    """Cached coder lookup; matrices are rebuilt once per
    (d, p, backend, code).  ``code`` is the per-part wire-format value
    ("rs" — the default and the only value old references carry — or
    "pm-msr", the product-matrix MSR regenerating code); an unknown
    value raises ErasureError so callers degrade to a clean read error
    instead of guessing at a foreign writer's math."""
    if code not in KNOWN_CODES:
        raise ErasureError(
            f"unknown erasure code {code!r} (this reader knows "
            f"{', '.join(KNOWN_CODES)})")
    be = get_backend(backend)
    key = (data, parity, be.name, code)
    with _CODER_LOCK:
        coder = _CODER_CACHE.get(key)
        if coder is None:
            if code == "pm-msr":
                from chunky_bits_tpu.ops.pm_msr import PMMSRCoder

                coder = PMMSRCoder(data, parity, be)
            else:
                coder = ErasureCoder(data, parity, be)
            _CODER_CACHE[key] = coder
        return coder
