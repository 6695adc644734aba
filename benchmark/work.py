"""The yardstick for the codec's device programs: the work a call's
shapes fix, and the chip's peaks.

A GF(2^8) matrix apply of an ``[r_out, k_in]`` matrix to ``B`` parts of
``S``-byte shards reads ``k_in*B*S`` bytes and writes ``r_out*B*S``, and
in the bit-matrix form over GF(2) (each byte a vector of 8 bits, each
coefficient an 8 x 8 bit block) it takes ``2 * (8 r_out) * (8 k_in)``
operations per column: a multiply and an add for each bit pair.  The
count is the same whatever implements the apply (the Pallas kernel, the
einsum path, an XOR schedule), so a kernel that does more work than the
math needs reads as a lower share, never a higher one.
"""

from __future__ import annotations

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add "
                       f"it to benchmark/work.py with its source")
    return PEAKS[device_kind]


def gf_apply_work(b: int, k_in: int, r_out: int, s: int
                  ) -> tuple[float, float]:
    """``(operations, bytes)`` of one ``[r_out, k_in]`` apply over
    ``[b, k_in, s]``."""
    return 128.0 * k_in * r_out * b * s, float((k_in + r_out) * b * s)


def least_seconds(ops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    compute = ops / peak["int8_ops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "int8") if compute >= memory else (memory, "hbm")
