"""Planted faults and the control, for the tests and ``control.py``; the
benchmark's own runs use none of them.

Each is a context manager that breaks the timed path underneath the
gateway, at the codec (``ErasureCoder``, ``ops/backend.py``, and the
``pm-msr`` coder's own decode and regeneration, ``ops/pm_msr.py``)
unless said otherwise, and keeps the store self-consistent (recorded
digests are recomputed from the broken bytes), so that only the
comparison with the reference can see it:

* ``altered``: one byte of every part's first output row flipped where
  the codec produces it (a parity byte on PUT, a rebuilt byte on GET or
  in a repair);
* ``unchanged``: the codec leaves its output as it found it (zeros);
* ``half_batch``: the second half of every batch, rounded up, is left
  out: its outputs stay zero;
* ``no_exchange``: the collective between chips is left out
  (``jax.lax.psum`` becomes the identity for the mesh programs);
* ``control``: the guarantee a PUT's 200 states is broken: one parity
  chunk file of every part is deleted before the PUT is acknowledged.
  On a GET cell the control is ``altered``: the exact-bytes guarantee.
  On a repair cell, the guarantee a repair's success states: each
  rebuilt chunk file is deleted once its write returns, before the job
  is recorded; for a scrub, the writes of the repair planner's in-place
  plans and of the resilver it falls back to alike.
"""

from __future__ import annotations

import contextlib
import hashlib

import numpy as np


def _break(out: np.ndarray, how: str) -> np.ndarray:
    """A broken copy of codec output ``[B, r, S]``."""
    out = np.array(out, dtype=np.uint8, copy=True)
    b = out.shape[0]
    if how == "altered":
        out[:, 0, 0] ^= 1
    elif how == "unchanged":
        out[:] = 0
    elif how == "half_batch":
        out[b // 2:] = 0
    return out


def _rehash(parity: np.ndarray, digests: np.ndarray, d: int) -> np.ndarray:
    digests = np.array(digests, copy=True)
    for bi in range(parity.shape[0]):
        for i in range(parity.shape[1]):
            digests[bi, d + i] = np.frombuffer(
                hashlib.sha256(parity[bi, i]).digest(), dtype=np.uint8)
    return digests


@contextlib.contextmanager
def _codec(how: str):
    from chunky_bits_tpu.ops.backend import ErasureCoder
    from chunky_bits_tpu.ops.pm_msr import PMMSRCoder

    enc1 = ErasureCoder.encode_hash_batch
    encn = ErasureCoder.encode_hash_batches
    dec = ErasureCoder.reconstruct_batch_picked
    msr_dec = PMMSRCoder.reconstruct_batch_picked
    msr_regen = PMMSRCoder.repair_batch

    def encode_hash_batch(self, data):
        parity, digests = enc1(self, data)
        parity = _break(parity, how)
        return parity, _rehash(parity, digests, self.data)

    def encode_hash_batches(self, batches):
        return [encode_hash_batch(self, b) for b in batches]

    def reconstruct_batch_picked(self, picked, present, wanted):
        return _break(dec(self, picked, present, wanted), how)

    def msr_reconstruct_batch_picked(self, picked, present, wanted):
        return _break(msr_dec(self, picked, present, wanted), how)

    def repair_batch(self, failed, helpers, projections):
        # the regenerated chunk [B, S] as one output row
        out = msr_regen(self, failed, helpers, projections)
        return _break(out[:, None, :], how)[:, 0, :]

    ErasureCoder.encode_hash_batch = encode_hash_batch
    ErasureCoder.encode_hash_batches = encode_hash_batches
    ErasureCoder.reconstruct_batch_picked = reconstruct_batch_picked
    PMMSRCoder.reconstruct_batch_picked = msr_reconstruct_batch_picked
    PMMSRCoder.repair_batch = repair_batch
    try:
        yield
    finally:
        ErasureCoder.encode_hash_batch = enc1
        ErasureCoder.encode_hash_batches = encn
        ErasureCoder.reconstruct_batch_picked = dec
        PMMSRCoder.reconstruct_batch_picked = msr_dec
        PMMSRCoder.repair_batch = msr_regen


@contextlib.contextmanager
def _no_exchange():
    import jax

    from chunky_bits_tpu.parallel import mesh

    psum = jax.lax.psum
    cached = [f for f in vars(mesh).values() if hasattr(f, "cache_clear")]
    jax.lax.psum = lambda x, axis_name, **kw: x
    for f in cached:
        f.cache_clear()
    try:
        yield
    finally:
        jax.lax.psum = psum
        for f in cached:
            f.cache_clear()


@contextlib.contextmanager
def _control_put():
    import os

    from chunky_bits_tpu.file.file_part import FilePart

    orig = FilePart.write_with_coder

    async def write_with_coder(*args, **kwargs):
        part = await orig(*args, **kwargs)
        for loc in part.parity[-1].locations:
            os.remove(loc.target)
        return part

    FilePart.write_with_coder = staticmethod(write_with_coder)
    try:
        yield
    finally:
        FilePart.write_with_coder = staticmethod(orig)


@contextlib.contextmanager
def _control_resilver():
    import os

    from chunky_bits_tpu.cluster.destination import ClusterWriter

    orig = ClusterWriter.write_shard

    async def write_shard(self, hash_, data):
        locations = await orig(self, hash_, data)
        for loc in locations:
            os.remove(loc.target)
        return locations

    ClusterWriter.write_shard = write_shard
    try:
        yield
    finally:
        ClusterWriter.write_shard = orig


@contextlib.contextmanager
def _control_scrub():
    import os

    from chunky_bits_tpu.cluster.repair import RepairPlanner

    orig = RepairPlanner._write_victims

    async def _write_victims(self, chunk, payload, victims, cx, code="rs"):
        out = await orig(self, chunk, payload, victims, cx, code)
        for victim in victims:
            try:
                os.remove(victim.target)
            except FileNotFoundError:
                pass  # a write that failed left nothing
        return out

    RepairPlanner._write_victims = _write_victims
    try:
        with _control_resilver():
            yield
    finally:
        RepairPlanner._write_victims = orig


FAULTS = ("altered", "unchanged", "half_batch", "no_exchange")


def plant(name: str, traffic: dict):
    """The context manager of fault ``name`` (or ``control``)."""
    if name == "control":
        ops = traffic["op"]
        ops = {o for o, _ in ops} if isinstance(ops, list) else {ops}
        if "scrub" in ops:
            return _control_scrub()
        if "resilver" in ops:
            return _control_resilver()
        return _control_put() if "put" in ops else _codec("altered")
    if name == "no_exchange":
        return _no_exchange()
    if name in FAULTS:
        return _codec(name)
    raise KeyError(f"unknown fault {name!r}")
