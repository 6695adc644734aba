"""Object bytes from the seed, shared by the load generator, the store
set-up and the reference check.

Every object is a slice of one seeded random pool at a seeded offset,
with a stamp of (object index, block index) written every 4 KiB.  So the
same (seed, index, size) gives the same bytes, and no chunk of one
object equals a chunk of another: chunks are content-addressed, and a
chunk already on disk would be skipped rather than written.
"""

from __future__ import annotations

import numpy as np

STAMP_EVERY = 4096
MIB = 1 << 20


class Payload:
    def __init__(self, seed: int, max_size: int) -> None:
        self.seed = seed
        self.pool = np.random.default_rng([seed, 0]).bytes(max_size + MIB)
        self.pool = np.frombuffer(self.pool, dtype=np.uint8)

    def offset(self, index: int, size: int) -> int:
        span = len(self.pool) - size
        return int(np.random.default_rng([self.seed, 1, index])
                   .integers(0, span + 1))

    def body(self, index: int, size: int) -> np.ndarray:
        """The bytes of object ``index`` (a fresh array)."""
        return self.body_into(index, size, np.empty(size, dtype=np.uint8))

    def body_into(self, index: int, size: int, out: np.ndarray
                  ) -> np.ndarray:
        """The bytes of object ``index``, written into the first ``size``
        bytes of ``out`` and returned as that slice: a buffer that is
        reused keeps its pages, where a fresh one of some hundreds of MiB
        pays a page fault every 4 KiB."""
        lo = self.offset(index, size)
        buf = out[:size]
        buf[:] = self.pool[lo:lo + size]
        words = buf[:size - size % 8].view(np.uint64)
        stride = STAMP_EVERY // 8
        n = -(-len(words) // stride)
        words[::stride] = ((np.uint64(index) << np.uint64(32))
                           | np.arange(n, dtype=np.uint64))
        return buf
