"""Fused Pallas TPU kernel for the GF(2^8) bit-plane transform.

The jnp einsum path (ops/jax_backend.py) materializes the bit expansion in
HBM: for every data byte it writes + reads 16 bytes of bf16 bit-planes, so
encode is HBM-bound at ~16x amplification.  This kernel fuses
unpack -> MXU matmul -> mod-2 -> pack inside VMEM, so HBM traffic drops to
read(data) + write(parity) — the roofline for this op.

Layout trick: bit-rows are ordered bit-major (row ``k*K + j`` = bit k of
shard j) so the unpack is 8 static sublane-slice stores and the pack is 8
static sublane-slice reads — no in-register transpose.  The host-side
matrix builder permutes the GF bit-matrix into this order.

Kernel math (per grid cell, shapes static):
    bits[K8, TS]  = unpack(data[K, TS])          (VPU shifts/ands)
    acc [R8, TS]  = m2[R8, K8] @ bits            (MXU, int8 -> int32 exact)
    out [R, TS]   = pack(acc & 1)                (VPU shifts/ors)

The matmul runs on the int8 MXU path (v5e executes int8 at 2x the bf16
rate, and the int8 bit-planes halve VMEM traffic vs bf16).  Hoist-proof
marginal measurement (bench.py method) on one v5e chip at d=10 p=4,
1 MiB chunks, batch 128: ~55-60 GiB/s sustained (two parts per grid
cell; tile/bblock swept on-chip), ~10% above the bf16 variant.  Variants
tried and rejected as slower on-chip: packed-word unpack via sublane
bitcast (~53), Kronecker-segmented matmul filling the MXU M dimension
(~53); int4 operands are unsupported by the runtime.  Round-4 re-sweep
(tile 8/16/32 KiB x bblock 1/2/4): flat plateau 51.5-54.6 with the
current (32 KiB, 2) at the top — no headroom left in these knobs; the
M=R8 dimension (32 rows at p=4) structurally caps MXU row utilization,
and block-diagonal multi-part stacking trades utilization for zero
FLOPs one-for-one, so it was not pursued.

Why ~13% MFU is the ceiling for this geometry, not a kernel defect:
the stationary weight tile is [K8, R8] = [80, 32] of the 128x128 MXU
array — 15.6% cell occupancy — and the measured 54 GiB/s is ~13.5% of
the int8 bound, i.e. the kernel runs the array at essentially full
streaming rate for the cells the math can occupy.  Transposing the
operands just moves the 32 to the other MXU dimension; padding K8/R8
to 128 adds zero-FLOP cells one-for-one with occupancy.  Only a wider
geometry fills it (d=16 -> K8=128; p=16 -> R8=128): at d=10,p=4 the
HBM roofline (~585 GiB/s data-rate at 14/10 traffic amplification) is
not the binding constraint, the weight aspect ratio is.
Accumulation is exact — each dot sums at most K8 ones, far below 2^31.
"""

from __future__ import annotations

import functools

import numpy as np

from chunky_bits_tpu.ops import gf256

# import jax lazily via function call to keep CLI startup light
_jax = None


def _jx():
    global _jax
    if _jax is None:
        import jax

        _jax = jax
    return _jax


def bit_matrix_bitmajor(mat: np.ndarray) -> np.ndarray:
    """Expand GF matrix [R, K] to GF(2) matrix [R*8, K*8] with bit-major
    row/col ordering: row ``b*R + i`` is bit b of output byte-row i, col
    ``b*K + j`` is bit b of input byte-row j."""
    r, k = mat.shape
    std = gf256.expand_to_bit_matrix(mat)  # row i*8+b, col j*8+b
    # new[b*r + i] = std[i*8 + b]; new[:, b*k + j] = std[:, j*8 + b]
    row_src = np.array([i * 8 + b for b in range(8) for i in range(r)])
    col_src = np.array([j * 8 + b for b in range(8) for j in range(k)])
    return std[row_src][:, col_src]


@functools.lru_cache(maxsize=256)
def _host_matrix(mat_bytes: bytes, r: int, k: int) -> np.ndarray:
    """Bit-major host matrix, cached per GF matrix so hot encode loops
    don't rebuild the expansion.  Only the (tiny, ~KBs) host->device copy
    happens per eager call — caching the *device* array here would leak
    tracers whenever the first call happens under a jit trace."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, k)
    return bit_matrix_bitmajor(mat).astype(np.int8)


@functools.lru_cache(maxsize=64)
def _build_packed_kernel(r: int, k: int, tile_s: int, bblock: int,
                         interpret: bool):
    """Field-multiplexed variant of the fused kernel: two data columns
    share one int8 MXU element.

    Column ``t`` of the tile's left half and column ``t + TS/2`` of its
    right half pack into one bit-plane element at bit offsets 0 and 6,
    and the contraction is split in half (block-diagonal weight
    ``[2*R8, K8]``), so each field's popcount stays <= ceil(K8/2) <= 63
    and the two fields never collide inside the int32 accumulator
    (``acc = P_lo + 64*P_hi`` exactly).  The dot then streams TS/2
    columns instead of TS through the MXU — at encode geometry
    (R8=32) the array spends half the column-passes of the standard
    kernel for the same math, and the bit-plane scratch halves too.
    Field extraction is exact: ``acc >> 6 == P_hi`` because
    ``P_lo < 64``, and ``(x + y) & 1 == (x ^ y) & 1`` recombines the
    two contraction halves' parities without a carry chain.

    Only valid when ``2*R8 <= 128`` (the doubled output keeps to one
    MXU weight tile — true for parity encode, p <= 8) and
    ``K8 <= 126`` (field popcounts fit 6 bits — d <= 15); callers gate
    and fall back to the standard kernel otherwise.
    """
    jax = _jx()
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r8, k8 = r * 8, k * 8
    kc = k8 // 2
    h = tile_s // 2

    def kernel(m2p_ref, data_ref, out_ref, bits_ref):
        for bi in range(bblock):
            data = data_ref[bi].astype(jnp.int32)  # [K, TS]
            lo = data[:, :h]
            hi = data[:, h:]
            for b in range(8):
                bits_ref[b * k:(b + 1) * k, :] = (
                    ((lo >> b) & 1) | (((hi >> b) & 1) << 6)
                ).astype(jnp.int8)
            acc = jax.lax.dot_general(
                m2p_ref[...], bits_ref[...],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [2*R8, h]
            a0 = acc[0:r8, :]
            a1 = acc[r8:2 * r8, :]
            lo_bits = (a0 ^ a1) & 1
            hi_bits = ((a0 >> 6) ^ (a1 >> 6)) & 1
            plo = lo_bits[0:r, :]
            phi = hi_bits[0:r, :]
            for b in range(1, 8):
                plo = plo | (lo_bits[b * r:(b + 1) * r, :] << b)
                phi = phi | (hi_bits[b * r:(b + 1) * r, :] << b)
            out_ref[bi, :, 0:h] = plo.astype(jnp.uint8)
            out_ref[bi, :, h:tile_s] = phi.astype(jnp.uint8)

    def gf_apply_pallas_packed(m2, data):
        batch, _k, s = data.shape
        # block-diagonal split of the contraction: rows 0..R8 see the
        # first kc bit-columns, rows R8..2*R8 the rest
        col = jnp.arange(k8, dtype=jnp.int32)[None, :]
        # lint: jit-hygiene-ok stacks two int8 [R8, K8] weight halves
        # along rows; no u8 lane axis is concatenated
        m2p = jnp.concatenate(
            [jnp.where(col < kc, m2, 0), jnp.where(col >= kc, m2, 0)],
            axis=0)  # [2*R8, K8] int8
        grid = (batch // bblock, s // tile_s)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((2 * r8, k8), lambda b, j: (0, 0)),
                pl.BlockSpec((bblock, k, tile_s), lambda b, j: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((bblock, r, tile_s),
                                   lambda b, j: (b, 0, j)),
            out_shape=jax.ShapeDtypeStruct((batch, r, s), jnp.uint8),
            scratch_shapes=[pltpu.VMEM((k8, h), jnp.int8)],
            interpret=interpret,
            name="gf_apply_pallas_packed",
        )(m2p, data)

    return jax.jit(gf_apply_pallas_packed)


@functools.lru_cache(maxsize=64)
def _build_kernel(r: int, k: int, tile_s: int, bblock: int, interpret: bool,
                  pack: bool = True):
    """``pack=True`` emits packed parity bytes [B, R, S] (the fused
    single-chip transform).  ``pack=False`` stops before the mod-2/pack
    and emits the raw popcount accumulator [B, R8, S] — the per-chip
    half of the contraction-sharded (tp) mesh path: partial popcounts
    from different chips *add* (GF(2^8) addition is XOR), so the mesh
    layer can ``psum`` them over ICI and apply one mod-2/pack after the
    collective (parallel/mesh.py).  The accumulator is int16: the MXU
    still accumulates in exact int32, but the global popcount is at most
    K8 <= 2048 ones, so narrowing before the HBM store halves both the
    accumulator's HBM traffic and the ICI bytes the tp psum moves."""
    jax = _jx()
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r8, k8 = r * 8, k * 8

    def kernel(m2_ref, data_ref, out_ref, bits_ref):
        # ``bblock`` parts per grid cell, reusing one bits scratch:
        # amortizes per-cell overhead (measured +5% at bblock=2 vs 1).
        for bi in range(bblock):
            data = data_ref[bi].astype(jnp.int32)  # [K, TS]
            for b in range(8):
                bits_ref[b * k:(b + 1) * k, :] = (
                    (data >> b) & 1
                ).astype(jnp.int8)
            acc = jax.lax.dot_general(
                m2_ref[...], bits_ref[...],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [R8, TS]
            if not pack:
                out_ref[bi] = acc.astype(jnp.int16)
                continue
            acc = acc & 1
            packed = acc[0:r, :]
            for b in range(1, 8):
                packed = packed | (acc[b * r:(b + 1) * r, :] << b)
            out_ref[bi] = packed.astype(jnp.uint8)

    out_rows, out_dtype = (r, jnp.uint8) if pack else (r8, jnp.int16)
    # stable program names for the device trace: the jitted wrapper's
    # module (``jit_<name>``) and the kernel's custom call
    name = "gf_apply_pallas" if pack else "gf_acc_pallas"

    def call(m2, data):
        batch, _k, s = data.shape
        grid = (batch // bblock, s // tile_s)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((r8, k8), lambda b, j: (0, 0)),
                pl.BlockSpec((bblock, k, tile_s), lambda b, j: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((bblock, out_rows, tile_s),
                                   lambda b, j: (b, 0, j)),
            out_shape=jax.ShapeDtypeStruct((batch, out_rows, s), out_dtype),
            scratch_shapes=[pltpu.VMEM((k8, tile_s), jnp.int8)],
            interpret=interpret,
            name=name,
        )(m2, data)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


def _pick_tile(s: int, k: int, row_bytes: int = 0) -> int:
    """Largest power-of-two tile dividing s, capped so the int8 bit-plane
    scratch (k*8 rows x tile lanes) stays within ~4 MiB of VMEM (s must be
    a multiple of 128 for the fast path; 32 KiB tiles measured fastest at
    d=10).  ``row_bytes`` adds a per-lane VMEM cost for the acc kernel's
    dot intermediate (int32, regardless of stored dtype), capped at
    ~6 MiB."""
    tile = 32768
    while tile > 128 and tile * k * 8 > (4 << 20):
        tile //= 2
    while tile > 128 and row_bytes and tile * row_bytes > (6 << 20):
        tile //= 2
    while tile > 128 and s % tile != 0:
        tile //= 2
    return tile if s % tile == 0 else 0


#: default for the field-multiplexed kernel at gated geometries — flip
#: after the real-chip A/B (exp_packed.py) shows a win; until then the
#: opt-in is $CHUNKY_BITS_TPU_PACKED_KERNEL=1
_PACKED_DEFAULT = False


def _packed_enabled() -> bool:
    """Standard env-flag parsing (cluster/tunables.env_flag): unset
    falls back to the process default; "", "0", "false", "no", "off"
    mean off.  Read at first dispatch and baked into jit caches — set
    before the first encode (PARITY.md)."""
    from chunky_bits_tpu.cluster.tunables import env_flag

    return env_flag("CHUNKY_BITS_TPU_PACKED_KERNEL",
                    default=_PACKED_DEFAULT)


def apply_m2_bitmajor(m2, shards, *, interpret: bool = False,
                      packed: bool | None = None):
    """Fused transform over an already-built bit-major int8 device matrix.

    The traceable core of ``apply_matrix_pallas``: usable inside
    ``shard_map`` local functions (parallel/mesh.py), where the matrix
    arrives as a device argument and shapes are static at trace time.
    ``m2`` is int8 [R*8, K*8] from ``bit_matrix_bitmajor``; ``shards`` is
    uint8 [B, K, S].  Raises ValueError when shapes don't fit the fast
    path.  ``packed`` selects the field-multiplexed kernel (None = the
    process default when the geometry is gated; selection is static at
    trace time).
    """
    r8, k8 = m2.shape
    r, k = r8 // 8, k8 // 8
    b, k2, s = shards.shape
    assert k2 == k
    if packed is None:
        packed = _packed_enabled() and packed_geometry_ok(r, k, s)
    if packed:
        return apply_m2_bitmajor_packed(m2, shards, interpret=interpret)
    tile = _pick_tile(s, k)
    if tile == 0 or r == 0:
        raise ValueError(f"shard size {s} not tileable for pallas path")
    bblock = 2 if b % 2 == 0 else 1
    fn = _build_kernel(r, k, tile, bblock, interpret)
    return fn(m2, shards)


def packed_geometry_ok(r: int, k: int, s: int) -> bool:
    """Gate for the field-multiplexed kernel: doubled output rows must
    keep to one MXU weight tile (2*R8 <= 128, i.e. r <= 8) and per-field
    popcounts must fit 6 bits (ceil(K8/2) <= 63, i.e. k <= 15); the
    column split needs lane-aligned tile halves (s a multiple of 256)."""
    return 0 < r <= 8 and 0 < k <= 15 and s % 256 == 0


def apply_m2_bitmajor_packed(m2, shards, *, interpret: bool = False):
    """Field-multiplexed fused transform (see ``_build_packed_kernel``):
    same contract as ``apply_m2_bitmajor``, restricted to geometries
    where ``packed_geometry_ok`` holds.  Raises ValueError otherwise."""
    r8, k8 = m2.shape
    r, k = r8 // 8, k8 // 8
    b, k2, s = shards.shape
    assert k2 == k
    if not packed_geometry_ok(r, k, s):
        raise ValueError(
            f"geometry r={r} k={k} s={s} outside the packed kernel's gate")
    # _pick_tile's VMEM budget is conservative here (the packed scratch
    # is [K8, tile/2], half the standard kernel's); tile halves stay
    # lane-aligned because the gate requires s % 256 == 0
    tile = _pick_tile(s, k)
    if tile < 256:
        raise ValueError(f"shard size {s} not tileable for packed path")
    bblock = 2 if b % 2 == 0 else 1
    fn = _build_packed_kernel(r, k, tile, bblock, interpret)
    return fn(m2, shards)


def acc_m2_bitmajor(m2, shards, *, interpret: bool = False):
    """Partial bit-plane accumulation (pre mod-2), bit-major rows:
    int16 [B, R*8, S] (exact — the global popcount is <= K8 <= 2048).
    Per-chip half of the tp-sharded mesh encode."""
    r8, k8 = m2.shape
    r, k = r8 // 8, k8 // 8
    b, k2, s = shards.shape
    assert k2 == k
    bblock = 2 if b % 2 == 0 else 1
    # budget at int32 cost: the dot intermediate is int32 in VMEM even
    # though the stored accumulator is int16
    tile = _pick_tile(s, k, row_bytes=r8 * 4 * bblock)
    if tile == 0 or r == 0:
        raise ValueError(f"shard size {s} not tileable for pallas path")
    fn = _build_kernel(r, k, tile, bblock, interpret, pack=False)
    return fn(m2, shards)


def pack_acc_bitmajor(acc):
    """Pack bit-major popcounts [B, R*8, S] (any integer dtype) into
    bytes [B, R, S]: row ``b*R + i`` is bit b of output byte-row i (the
    layout ``bit_matrix_bitmajor`` produces), so the mod-2 bits of plane
    b land at bit position b of byte i."""
    import jax.numpy as jnp

    b, r8, s = acc.shape
    r = r8 // 8
    bits = (acc & 1).astype(jnp.int32).reshape(b, 8, r, s)
    shifts = jnp.arange(8, dtype=jnp.int32)
    return jnp.sum(bits << shifts[None, :, None, None],
                   axis=1).astype(jnp.uint8)


def bitmajor_device_matrix(mat: np.ndarray):
    """The int8 bit-major device matrix for a GF matrix [R, K] (host
    expansion cached; the tiny host->device copy happens per call)."""
    import jax.numpy as jnp

    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    return jnp.asarray(_host_matrix(mat.tobytes(), *mat.shape),
                       dtype=jnp.int8)


def apply_matrix_pallas(mat: np.ndarray, shards, *, interpret: bool = False):
    """Device-side bit-plane transform via the fused kernel.

    ``mat`` is the GF(2^8) matrix [R, K]; ``shards`` is a jax or numpy
    uint8 array [B, K, S] with S a multiple of 128.  Returns a jax uint8
    array [B, R, S].  Raises ValueError when shapes don't fit the fast
    path.
    """
    import jax.numpy as jnp

    return apply_m2_bitmajor(bitmajor_device_matrix(mat),
                             jnp.asarray(shards), interpret=interpret)
