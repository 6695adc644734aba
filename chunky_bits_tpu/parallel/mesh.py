"""Mesh-sharded erasure math: the multi-chip compute plane.

The reference scales with HTTP fan-out across storage nodes
(src/cluster/writer.rs); the TPU rebuild scales the *math* across chips with
``jax.sharding`` + ``shard_map`` over a 2D mesh:

* ``dp`` — the part-batch axis: each chip encodes its own slice of parts
  (data-parallel; parts are independent stripes, reference
  src/file/writer.rs:208 encodes them one-by-one on one core).
* ``sp`` — the shard-byte axis: GF(2^8) transforms are element-wise across
  bytes, so a single huge stripe can be split across chips the way sequence
  parallelism splits a long context — each chip transforms its byte range,
  no halo exchange needed.
* ``tp`` — the stripe axis (wide stripes, BASELINE.md config 5: d=20 p=6
  over a v5e-8): the *contraction* dimension of the GF matmul is split, so
  each chip holds d/tp data shards and computes a partial bit-plane
  product; full parity emerges from an integer ``psum`` over ``tp``
  followed by a single mod-2 — exact because GF(2^8) addition is XOR and
  popcounts add over chips.  This is the tensor-parallel decomposition of
  erasure coding: the per-chip working set shrinks with the stripe width,
  and the only cross-chip traffic is the [B, p*8, S] accumulator riding
  ICI (int16 on the pallas impl — exact, since the global popcount is at
  most d*8 <= 2048 — halving the psum bytes).

The bit-matrix is tiny (<=2048x2048 bits) and replicated (column-sharded
over ``tp`` in the wide-stripe path).  Collectives are the ``tp`` psum and a
checksum psum used to validate mesh execution; shards ride ICI, never DCN.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.obs import tracing as obs_tracing
from chunky_bits_tpu.ops import gf256


def _smap(fn, *, mesh, in_specs, out_specs, name, impl="einsum"):
    """shard_map with the varying-axes checker disabled for the pallas
    impls: pallas_call's out_shape carries no vma annotation, so jax's
    check_vma rejects it; the out_specs here are explicit and the psum
    lowers to the same collective either way.  ``name`` is the
    program's stable name in the device trace (``jit_<name>``)."""
    import jax

    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs,
                           check_vma=impl == "einsum")

    def program(*args):
        return mapped(*args)

    program.__name__ = program.__qualname__ = name
    return program


def _put(mesh, spec, x):
    """Place ``x`` straight into its sharded layout: each device
    receives only its own slice (``jnp.asarray`` would land the whole
    block on device 0 first and leave XLA to move it across chips).
    Spanned as ``codec.h2d`` (obs/tracing.py)."""
    import jax
    from jax.sharding import NamedSharding

    nbytes = getattr(x, "nbytes", 0)
    with obs_tracing.span("codec.h2d", bytes=nbytes):
        placed = jax.device_put(x, NamedSharding(mesh, spec))
    obs_metrics.record_codec_transfer("h2d", nbytes)
    return placed


def _make_mesh_2d(n_devices, first, first_name, second, second_name,
                  devices=None):
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(
            f"mesh needs {n} devices, only {len(devices)} visible")
    devices = devices[:n]
    if first is None and second is None:
        second = 1
        first = n
    elif first is None:
        first = n // second
    elif second is None:
        second = n // first
    if first * second != n:
        raise ValueError(
            f"{first_name}({first}) * {second_name}({second}) "
            f"!= devices({n})")
    mesh_devices = np.array(devices).reshape(first, second)
    return Mesh(mesh_devices, (first_name, second_name))


def make_mesh(n_devices: Optional[int] = None, dp: Optional[int] = None,
              sp: Optional[int] = None, devices=None):
    """Build a ('dp', 'sp') mesh over the first n of ``devices``
    (default: all global devices)."""
    return _make_mesh_2d(n_devices, dp, "dp", sp, "sp", devices=devices)


def make_stripe_mesh(n_devices: Optional[int] = None,
                     dp: Optional[int] = None, tp: Optional[int] = None,
                     devices=None):
    """Build a ('dp', 'tp') mesh for wide-stripe (contraction-sharded)
    encode/decode; ``tp`` must divide the stripe's data-shard count."""
    return _make_mesh_2d(n_devices, dp, "dp", tp, "tp", devices=devices)


from chunky_bits_tpu.ops.bitplane import apply_bitplane as _apply_local
from chunky_bits_tpu.ops.bitplane import bitplane_acc as _acc_local
from chunky_bits_tpu.ops.bitplane import pack_acc as _pack_acc


@functools.lru_cache(maxsize=16)
def _host_bit_matrix(mat_bytes: bytes, r: int, k: int) -> np.ndarray:
    # host-side cache only: caching device arrays would leak tracers if
    # the first call happened under a jit trace
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, k)
    return gf256.expand_to_bit_matrix(mat).astype(np.float32)


def _device_bit_matrix(mat_bytes: bytes, r: int, k: int):
    import jax.numpy as jnp

    return jnp.asarray(_host_bit_matrix(mat_bytes, r, k),
                       dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# Per-chip transform implementations.
#
# On a TPU mesh each chip runs the fused Pallas kernel
# (ops/pallas_kernels.py — unpack/MXU-matmul/pack entirely in VMEM, the
# same kernel the single-chip jax backend runs), so the mesh path carries
# the single-chip roofline instead of falling back to the HBM-bound einsum
# expansion.  CPU meshes (the virtual 8-device test mesh) keep the einsum;
# "pallas_interpret" runs the kernel's interpret mode so the wiring is
# testable off-TPU.
# ---------------------------------------------------------------------------

_IMPLS = ("einsum", "pallas", "pallas_interpret")


def _check_impl(impl: str) -> None:
    if impl not in _IMPLS:
        raise ValueError(f"unknown mesh impl {impl!r} (want one of {_IMPLS})")


def _auto_impl(mesh, r: int, k_local: int, s_local: int) -> str:
    """Pick the per-chip transform: the fused Pallas kernel when the mesh
    lives on TPU chips and the local block fits its fast path, else the
    einsum bit-plane expansion."""
    from chunky_bits_tpu.ops.pallas_kernels import _pick_tile

    try:
        on_tpu = mesh.devices.flat[0].platform == "tpu"
    # lint: broad-except-ok platform probe only; a failure routes to the
    # einsum impl, which computes the same bytes
    except Exception:
        on_tpu = False
    if on_tpu and r > 0 and k_local > 0 and _pick_tile(s_local, k_local):
        return "pallas"
    return "einsum"


def _local_apply(impl: str):
    """The shard_map local function: bf16 standard-order matrix for the
    einsum impl, int8 bit-major matrix for the pallas impls."""
    if impl == "einsum":
        return _apply_local
    from chunky_bits_tpu.ops.pallas_kernels import apply_m2_bitmajor

    interp = impl == "pallas_interpret"

    def fn(m2, shards):
        return apply_m2_bitmajor(m2, shards, interpret=interp)

    return fn


def _device_matrix(impl: str, mat: np.ndarray):
    """Device matrix in the layout the impl's local function expects."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    if impl == "einsum":
        return _device_bit_matrix(mat.tobytes(), *mat.shape)
    from chunky_bits_tpu.ops.pallas_kernels import bitmajor_device_matrix

    return bitmajor_device_matrix(mat)


@functools.lru_cache(maxsize=32)
def _sharded_apply_fn(mesh, impl: str, donate: bool = False):
    """Jitted shard_mapped transform, cached per (mesh, impl, donate) so
    repeated calls reuse the XLA executable instead of retracing.
    ``donate`` hands the staged shards buffer back to the allocator
    (double-buffered dispatch keeps two in flight; donation halves the
    device-memory high-water mark) — TPU meshes only: on CPU jax may
    alias the caller's host numpy memory zero-copy, and donating an
    aliased buffer could corrupt it."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.jit(_smap(
        _local_apply(impl),
        mesh=mesh,
        in_specs=(P(None, None), P("dp", None, "sp")),
        out_specs=P("dp", None, "sp"),
        name="gf_apply_mesh",
        impl=impl,
    ), donate_argnums=(1,) if donate else ())


def sharded_apply(mesh, mat: np.ndarray, shards, *,
                  impl: Optional[str] = None, donate: bool = False):
    """out[B, R, S] = mat ⊗ shards with B split over 'dp' and S over 'sp'.

    Parts are independent and the transform is element-wise over S, so both
    shardings are embarrassingly parallel — XLA inserts only the final
    all-gather to deliver the replicated-out result.  ``impl`` overrides
    the per-chip transform choice (tests force "pallas_interpret");
    ``donate`` releases the staged input buffer to the allocator (TPU
    meshes only — see ``_sharded_apply_fn``).
    """
    from jax.sharding import PartitionSpec as P

    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    r, k = mat.shape
    s = shards.shape[2]
    if impl is None:
        impl = _auto_impl(mesh, r, k, s // mesh.shape["sp"])
    _check_impl(impl)
    m2 = _device_matrix(impl, mat)
    placed = _put(mesh, P("dp", None, "sp"), shards)
    with obs_tracing.span("codec.launch"):
        return _sharded_apply_fn(mesh, impl, donate)(m2, placed)


@functools.lru_cache(maxsize=32)
def _encode_step_fn(mesh, impl: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    local = _local_apply(impl)

    def step(m2, shards):
        parity = local(m2, shards)
        local_sum = parity.astype(jnp.uint32).sum()
        checksum = jax.lax.psum(jax.lax.psum(local_sum, "dp"), "sp")
        return parity, checksum

    return jax.jit(_smap(
        step,
        mesh=mesh,
        in_specs=(P(None, None), P("dp", None, "sp")),
        out_specs=(P("dp", None, "sp"), P()),
        name="gf_encode_step_mesh",
        impl=impl,
    ))


def encode_step_sharded(mesh, encode_matrix: np.ndarray, data,
                        *, impl: Optional[str] = None):
    """One full sharded ingest compute step: parity for every part plus a
    psum'd global checksum (the cross-chip collective exercised over ICI).

    ``data`` is uint8 [B, d, S]; returns (parity [B, p, S], checksum).
    """
    from jax.sharding import PartitionSpec as P

    d = encode_matrix.shape[1]
    parity_rows = np.ascontiguousarray(encode_matrix[d:], dtype=np.uint8)
    if impl is None:
        impl = _auto_impl(mesh, parity_rows.shape[0], d,
                          data.shape[2] // mesh.shape["sp"])
    _check_impl(impl)
    m2 = _device_matrix(impl, parity_rows)
    return _encode_step_fn(mesh, impl)(
        m2, _put(mesh, P("dp", None, "sp"), data))


# ---------------------------------------------------------------------------
# Wide-stripe (contraction-sharded) path — BASELINE.md config 5.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _wide_apply_fn(mesh, impl: str, donate: bool = False):
    """Jitted transform with the GF contraction split over 'tp'.

    Each chip holds a [B/dp, K/tp, S] slice of the input shards and the
    matching column block of the bit-matrix; it computes the partial
    popcount accumulation, which is integer-``psum``'d over 'tp'
    (popcounts add across chips because GF(2^8) addition is XOR) and packed
    to bytes with one final mod-2.  Output is replicated within each 'tp'
    group — every chip in the group ends up with the full parity for its
    'dp' slice of parts, ready for the host gather.

    The einsum impl column-shards one standard-order bf16 bit-matrix with
    ``P(None, 'tp')``.  The pallas impls run the fused accumulation kernel
    (``acc_m2_bitmajor``) per chip; bit-major column order interleaves
    byte columns, so the host pre-splits the GF matrix into per-chip byte
    column blocks, expands each to bit-major, and ships them stacked
    [tp, R8, K8/tp] sharded ``P('tp', None, None)``.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if impl == "einsum":
        def step(m2_cols, shards_local):
            acc = _acc_local(m2_cols, shards_local)
            acc = jax.lax.psum(acc, "tp")
            return _pack_acc(acc)

        m2_spec = P(None, "tp")
    else:
        from chunky_bits_tpu.ops.pallas_kernels import (acc_m2_bitmajor,
                                                        pack_acc_bitmajor)

        interp = impl == "pallas_interpret"

        def step(m2_blocks, shards_local):
            acc = acc_m2_bitmajor(m2_blocks[0], shards_local,
                                  interpret=interp)
            acc = jax.lax.psum(acc, "tp")
            return pack_acc_bitmajor(acc)

        m2_spec = P("tp", None, None)

    return jax.jit(_smap(
        step,
        mesh=mesh,
        in_specs=(m2_spec, P("dp", "tp", None)),
        out_specs=P("dp", None, None),
        name="gf_apply_mesh_wide",
        impl=impl,
    ), donate_argnums=(1,) if donate else ())


@functools.lru_cache(maxsize=16)
def _host_bitmajor_blocks(mat_bytes: bytes, r: int, k: int,
                          tp: int) -> np.ndarray:
    """Per-chip bit-major column blocks [tp, R8, (K/tp)*8] for the pallas
    wide-stripe path."""
    from chunky_bits_tpu.ops.pallas_kernels import bit_matrix_bitmajor

    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, k)
    kb = k // tp
    blocks = [
        bit_matrix_bitmajor(np.ascontiguousarray(mat[:, t * kb:(t + 1) * kb]))
        for t in range(tp)
    ]
    return np.stack(blocks).astype(np.int8)


def wide_apply_sharded(mesh, mat: np.ndarray, shards,
                       *, impl: Optional[str] = None,
                       donate: bool = False):
    """out[B, R, S] = mat ⊗ shards with B over 'dp' and the K (stripe)
    axis over 'tp'.  ``mat`` is a GF(2^8) matrix [R, K] (parity rows for
    encode, host-inverted rows for decode — the same primitive serves
    both, like the reference's encode_sep/reconstruct pair at
    src/file/file_part.rs:161,302).  'tp' must divide K.  ``donate``
    releases the staged input buffer to the allocator (TPU meshes only —
    see ``_sharded_apply_fn``).
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    r, k = mat.shape
    if k % tp != 0:
        raise ValueError(f"stripe width {k} not divisible by tp={tp}")
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    if impl is None:
        impl = _auto_impl(mesh, r, k // tp, shards.shape[2])
    _check_impl(impl)
    if impl == "einsum":
        m2 = _device_bit_matrix(mat.tobytes(), r, k)
    else:
        m2 = jnp.asarray(_host_bitmajor_blocks(mat.tobytes(), r, k, tp),
                         dtype=jnp.int8)
    placed = _put(mesh, P("dp", "tp", None), shards)
    with obs_tracing.span("codec.launch"):
        return _wide_apply_fn(mesh, impl, donate)(m2, placed)


def encode_wide_sharded(mesh, encode_matrix: np.ndarray, data,
                        *, impl: Optional[str] = None):
    """Wide-stripe parity: data uint8 [B, d, S] with d split over 'tp'
    (and B over 'dp') -> parity uint8 [B, p, S]."""
    d = encode_matrix.shape[1]
    parity_rows = np.ascontiguousarray(encode_matrix[d:], dtype=np.uint8)
    return wide_apply_sharded(mesh, parity_rows, data, impl=impl)
