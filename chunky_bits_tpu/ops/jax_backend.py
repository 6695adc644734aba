"""JAX/TPU erasure backend: GF(2^8) as batched bit-plane matmuls.

The idea (TPU-first, not a translation of the reference's byte-table SIMD):
GF(2^8) is an 8-dim vector space over GF(2), and multiplying by a constant is
GF(2)-linear.  Expanding the (d+p) x d byte matrix into an 8x-larger binary
matrix turns the whole Reed-Solomon transform into

    out_bits[B, r*8, S] = M2[r*8, k*8] @ bits[B, k*8, S]   (mod 2)

— a plain matmul with 0/1 operands, which is exactly what the MXU is for.
Products are 0/1 and the contraction length is k*8 <= 2048, so bf16 inputs
with f32 accumulation are exact; the mod-2 and the byte pack/unpack are cheap
VPU element-wise ops that XLA fuses around the matmul.

The same primitive serves encode (parity rows) and decode (host-inverted
rows), replacing the reference's CPU hot loops at
src/file/file_part.rs:161-165 (encode_sep) and :128,302 (reconstruct).

Multi-chip: parts are independent, so scaling is a shard_map over the batch
axis with the bit-matrix replicated (parallel/mesh.py, ops/mesh_backend.py).

A device failure (init, compile, dispatch) raises; this backend never swaps
itself for another codec.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from chunky_bits_tpu.errors import ErasureError
from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.obs import tracing as obs_tracing
from chunky_bits_tpu.ops import gf256
from chunky_bits_tpu.ops.backend import ErasureBackend

# Deferred jax import: the CLI must not pay jax start-up unless this backend
# is actually selected.
_jax = None
_jnp = None
_IMPORT_LOCK = threading.Lock()


def _ensure_jax():
    global _jax, _jnp
    if _jax is None:
        with _IMPORT_LOCK:
            if _jax is None:
                import jax
                import jax.numpy as jnp

                _jax, _jnp = jax, jnp
    return _jax, _jnp


_APPLY_FN = None


def _jitted_apply():
    """Build the jitted bit-plane transform once per process."""
    global _APPLY_FN
    if _APPLY_FN is not None:
        return _APPLY_FN
    jax, _ = _ensure_jax()
    from chunky_bits_tpu.ops.bitplane import apply_bitplane

    def gf_apply_einsum(m2, shards):
        # the program's stable name in the device trace
        # (``jit_gf_apply_einsum``)
        return apply_bitplane(m2, shards)

    _APPLY_FN = jax.jit(gf_apply_einsum)
    return _APPLY_FN


class JaxBackend(ErasureBackend):
    """Erasure math on the default JAX device (TPU when present)."""

    name = "jax"

    #: batchers should merge concurrent requests into one dispatch —
    #: per-dispatch overhead dwarfs the host-side concatenate copy
    prefers_merged_batches = True

    #: cap device memory per dispatch: bits blow bytes up 8x as bf16 (16x B)
    max_block_bytes = 64 << 20

    #: decode matrices are one-per-erasure-pattern; bound the device cache so
    #: a long-running resilver over many patterns cannot grow memory forever.
    max_cached_matrices = 256

    def __init__(self) -> None:
        from chunky_bits_tpu.utils.compile_cache import enable_compile_cache

        jax, _ = _ensure_jax()
        enable_compile_cache()
        self._m2_cache: OrderedDict[bytes, object] = OrderedDict()
        self._fused_cache: OrderedDict[tuple, object] = OrderedDict()
        self._lock = threading.Lock()
        # 128-aligned shard sizes on a TPU take the fused Pallas kernel
        # (ops/pallas_kernels.py — a TPU-only Mosaic kernel); everything
        # else, including GPU backends, takes the einsum path.
        self._on_tpu = jax.default_backend() == "tpu"

    def _bit_matrix(self, mat: np.ndarray):
        jax, jnp = _ensure_jax()
        key = mat.tobytes() + bytes(mat.shape[0:1])
        with self._lock:
            cached = self._m2_cache.get(key)
            if cached is not None:
                self._m2_cache.move_to_end(key)
                return cached
        m2 = gf256.expand_to_bit_matrix(mat).astype(np.float32)
        dev = jnp.asarray(m2, dtype=jnp.bfloat16)
        with self._lock:
            self._m2_cache[key] = dev
            while len(self._m2_cache) > self.max_cached_matrices:
                self._m2_cache.popitem(last=False)
        return dev

    def apply_matrix(self, mat: np.ndarray, shards: np.ndarray,
                     on_block=None) -> np.ndarray:
        b, k, s = shards.shape
        r = mat.shape[0]
        if r == 0 or b == 0:
            return np.zeros((b, r, s), dtype=np.uint8)
        if self._on_tpu and s % 128 == 0 and s >= 1024:
            try:
                return self._apply_pallas_blocked(mat, shards, on_block)
            # lint: broad-except-ok re-raised as ErasureError with the
            # cause chained; nothing is recomputed on another path
            except Exception as err:
                raise ErasureError(
                    f"pallas erasure dispatch failed on a [{b}, {k}, {s}] "
                    f"batch: {err}") from err
        m2 = self._bit_matrix(mat)
        fn = _jitted_apply()
        # Block the batch axis so the 16x bit expansion fits device memory
        # (halved: the double-buffered pipeline keeps 2 blocks in flight).
        per_item = k * s * 16
        block = max(1, self.max_block_bytes // 2 // max(per_item, 1))
        return self._pipelined_blocks(lambda dev: fn(m2, dev),
                                      shards, block, on_block)

    def _pipelined_blocks(self, dispatch, shards: np.ndarray,
                          block: int, on_block=None):
        """Run ``dispatch`` over batch blocks with H2D/compute overlap:
        jax dispatch is asynchronous, so issuing block N+1's device_put
        and kernel before materializing block N's result lets the next
        host->device transfer (and compute) proceed while the host blocks
        on the previous device->host copy.  Two blocks in flight — the
        classic double buffer.  ``on_block(lo, arr)`` fires as each
        output block materializes, so callers can overlap host
        post-processing (shard hashing) with the remaining device work,
        on the caller's thread.  ``dispatch`` may return one array or a tuple of arrays (the
        fused encode+hash path); tuple outputs are concatenated per
        element, and ``on_block`` must be None for them.

        Each block is three spans (obs/tracing.py): ``codec.h2d`` (the
        ``device_put``), ``codec.launch`` (the jitted call, which only
        enqueues) and ``codec.collect`` (the wait for the device and the
        device-to-host copy)."""
        jax, _ = _ensure_jax()

        def put(x):
            with obs_tracing.span("codec.h2d", bytes=x.nbytes):
                dev = jax.device_put(x)
            obs_metrics.record_codec_transfer("h2d", x.nbytes)
            return dev

        def launch(dev):
            with obs_tracing.span("codec.launch"):
                return dispatch(dev)

        def materialize(o):
            # the device->host copy of a TPU array can come back strided
            # (seen for [B, 3, S] uint8 parity on a v5e); host hashing
            # writes through flat views, so blocks leave here contiguous
            with obs_tracing.span("codec.collect") as collect:
                if isinstance(o, tuple):
                    assert on_block is None
                    out = tuple(np.ascontiguousarray(a) for a in o)
                    nbytes = sum(a.nbytes for a in out)
                else:
                    out = np.ascontiguousarray(o)
                    nbytes = out.nbytes
                collect.end(bytes=nbytes)
            obs_metrics.record_codec_transfer("d2h", nbytes)
            return out

        b = shards.shape[0]
        if b <= block:
            out = materialize(launch(put(shards)))
            if on_block is not None:
                on_block(0, out)
            return out
        outs = []
        pending = []
        for lo in range(0, b, block):
            dev = put(np.ascontiguousarray(shards[lo:lo + block]))
            pending.append(launch(dev))
            if len(pending) > 1:
                arr = materialize(pending.pop(0))
                if on_block is not None:
                    on_block(len(outs) * block, arr)
                outs.append(arr)
        for o in pending:
            arr = materialize(o)
            if on_block is not None:
                on_block(len(outs) * block, arr)
            outs.append(arr)
        if isinstance(outs[0], tuple):
            return tuple(np.concatenate([o[i] for o in outs], axis=0)
                         for i in range(len(outs[0])))
        return np.concatenate(outs, axis=0)

    #: the fused kernel keeps bits in VMEM, so its device footprint is just
    #: data + parity; a much larger per-dispatch budget applies.
    max_pallas_block_bytes = 2 << 30

    def _apply_pallas_blocked(self, mat: np.ndarray, shards,
                              on_block=None) -> np.ndarray:
        from chunky_bits_tpu.ops.pallas_kernels import apply_matrix_pallas

        b, k, s = shards.shape
        per_item = k * s * 2
        block = max(1, self.max_pallas_block_bytes // 2 // max(per_item, 1))
        return self._pipelined_blocks(
            lambda dev: apply_matrix_pallas(mat, dev), shards, block,
            on_block)

    @staticmethod
    def _device_sha_enabled() -> bool:
        """Opt-in for hashing shards on the device inside the encode
        dispatch ($CHUNKY_BITS_TPU_DEVICE_SHA=1) — default off until an
        on-chip A/B (exp_devsha.py) shows it beating host SHA x cores.
        Read at dispatch time, but jit caches bake the routing into
        compiled executables, so set it before the first encode (same
        caveat as the packed-kernel flag, PARITY.md).  Exactly ``"1"``
        enables — deliberately stricter than env_flag's truthiness,
        matching the documented opt-in spelling for a path still
        pending its on-chip A/B."""
        from chunky_bits_tpu.cluster.tunables import env_str

        return env_str("CHUNKY_BITS_TPU_DEVICE_SHA") == "1"

    def _fused_encode_hash_fn(self, mat: np.ndarray, s: int,
                              interpret: bool = False):
        """Jitted ``u8[B, k, S] -> (parity u8[B, r, S],
        digests u8[B, k+r, 32])`` — parity and ALL shard digests in one
        device dispatch: bytes cross host->device once and only parity
        + 32 B/row digests come back.  SHA runs on the VPU, the GF
        matmul on the MXU; XLA overlaps them freely.  ``interpret``
        runs the pallas kernel in interpret mode (CPU tests).  Cached
        per (matrix, S, interpret) so repeat ingests reuse the compiled
        executable instead of re-tracing every dispatch."""
        key = (mat.tobytes(), mat.shape, s, interpret)
        with self._lock:
            cached = self._fused_cache.get(key)
        if cached is not None:
            return cached
        jax, jnp = _ensure_jax()
        from chunky_bits_tpu.ops.pallas_kernels import apply_matrix_pallas
        from chunky_bits_tpu.ops.sha256_jax import make_sha256_aligned

        sha = make_sha256_aligned(s)
        r = mat.shape[0]

        def gf_encode_sha_pallas(dev):
            b, k, _ = dev.shape
            parity = apply_matrix_pallas(mat, dev, interpret=interpret)
            # lint: jit-hygiene-ok rows are s bytes with s % 128 == 0
            # (the pallas-path gate), so the concat is lane-aligned
            digests = sha(jnp.concatenate(
                [dev, parity], axis=1).reshape(b * (k + r), s))
            return parity, digests.reshape(b, k + r, 32)

        fn = jax.jit(gf_encode_sha_pallas)
        with self._lock:
            self._fused_cache[key] = fn
            while len(self._fused_cache) > self.max_cached_matrices:
                self._fused_cache.popitem(last=False)
        return fn

    def _encode_and_hash_device(
        self, mat: np.ndarray, shards: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """The all-on-device ingest: the shared double-buffered block
        walk, each dispatch returning (parity, digests)."""
        b, k, s = shards.shape
        r = mat.shape[0]
        fn = self._fused_encode_hash_fn(mat, s)
        # resident per item: data + parity + the concatenated copy the
        # SHA hashes over = 2*(k+r)*s bytes (vs k*s*2 on the plain
        # parity path)
        per_item = 2 * (k + r) * s
        block = max(1, self.max_pallas_block_bytes // 2 // per_item)
        return self._pipelined_blocks(fn, shards, block)

    def encode_and_hash(
        self, mat: np.ndarray, shards: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Overlapped ingest: the device computes parity while the host
        hashes the data rows, and each parity block is hashed as it lands
        while later blocks are still in flight.  The generic fallback
        (ops/backend.py) runs encode-then-hash strictly serially, leaving
        the host idle during device compute — the reference's CPU path is
        serial too (src/file/file_part.rs:161,185).  Output is identical
        to the fused native engine's, bit for bit.

        With $CHUNKY_BITS_TPU_DEVICE_SHA=1 (and a 64-aligned shard size
        on the pallas path) the digests are computed ON the device in
        the same dispatch as the parity — the host's per-core SHA bound
        drops out of the pipeline entirely."""
        from chunky_bits_tpu.ops.backend import row_hasher

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
        if (self._device_sha_enabled() and self._on_tpu
                and s % 128 == 0 and s >= 1024):
            # same eligibility gate as the pallas parity path, so the
            # fused dispatch never mixes kernels mid-batch
            try:
                return self._encode_and_hash_device(mat, shards)
            # lint: broad-except-ok re-raised as ErasureError with the
            # cause chained; nothing is recomputed on another path
            except Exception as err:
                raise ErasureError(
                    f"device SHA encode+hash failed on a [{b}, {k}, {s}] "
                    f"batch: {err}") from err
        # host hashing overlaps the in-flight device dispatch on the
        # shared host pipeline's daemon workers (sliced across them),
        # the same overlap the retired 2-worker ThreadPoolExecutor
        # provided — CB103-clean and observable in the stage counters
        from chunky_bits_tpu.parallel.host_pipeline import (
            get_host_pipeline,
            join_jobs,
        )

        pipe = get_host_pipeline()
        jobs = list(pipe.hash_rows_jobs(shards, data_digests))

        def on_block(lo, arr):
            # axis-0 slices of the C-contiguous digest array are
            # contiguous, so the hasher can write in place
            jobs.extend(pipe.hash_rows_jobs(
                arr, parity_digests[lo:lo + arr.shape[0]]))

        parity = self.apply_matrix(mat, shards, on_block=on_block)
        with obs_tracing.span("codec.hash_join"):
            join_jobs(jobs)
        return parity, np.concatenate([data_digests, parity_digests],
                                      axis=1)
