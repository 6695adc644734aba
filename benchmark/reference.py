"""The plain reference: Reed-Solomon over GF(2^8) and SHA-256, written
from the published definitions and importing nothing of the program.

Field: GF(2^8) with the polynomial x^8 + x^4 + x^3 + x^2 + 1 (0x11d)
and generator 2, as in the ``reed-solomon-erasure`` crate that the
reference Chunky-Bits uses.  Encoding matrix: the (d+p) x d Vandermonde
matrix ``V[r][c] = r^c``, times the inverse of its top d x d square, so
the first d rows are the identity (Backblaze's construction).  A part of
``n`` bytes is split into d shards of ``ceil(n / d)`` bytes, the last
zero-padded; each chunk's address is the SHA-256 of its bytes.
"""

from __future__ import annotations

import hashlib

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[(255 - LOG[a]) % 255])


def power(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def matmul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        acc = [0] * len(b[0])
        for j, coef in enumerate(row):
            if coef:
                for c, v in enumerate(b[j]):
                    acc[c] ^= mul(coef, v)
        out.append(acc)
    return out


def invert(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan elimination over GF(2^8)."""
    n = len(m)
    aug = [list(row) + [int(i == j) for j in range(n)]
           for i, row in enumerate(m)]
    for col in range(n):
        pivot = next(r for r in range(col, n) if aug[r][col])
        aug[col], aug[pivot] = aug[pivot], aug[col]
        scale = inv(aug[col][col])
        aug[col] = [mul(scale, v) for v in aug[col]]
        for r in range(n):
            f = aug[r][col]
            if r != col and f:
                aug[r] = [v ^ mul(f, w) for v, w in zip(aug[r], aug[col])]
    return [row[n:] for row in aug]


def parity_matrix(d: int, p: int) -> list[list[int]]:
    """The p parity rows of the systematic encoding matrix."""
    vand = [[power(r, c) for c in range(d)] for r in range(d + p)]
    return matmul(vand, invert(vand[:d]))[d:]


_LOW7 = np.uint64(0xFEFEFEFEFEFEFEFE)
_BIT0 = np.uint64(0x0101010101010101)
_REDUCE = np.uint64(POLY & 0xFF)


def _times2(w: np.ndarray) -> np.ndarray:
    """Each byte of the uint64 words times 2 in GF(2^8) (shift, and
    reduce the bytes whose top bit fell out)."""
    return ((w << np.uint64(1)) & _LOW7) ^ (((w >> np.uint64(7)) & _BIT0)
                                            * _REDUCE)


def encode(shards: np.ndarray, rows: list[list[int]]) -> np.ndarray:
    """Parity ``[p, S]`` of data shards ``[d, S]`` (uint8), by
    shift-and-add: ``c * x`` is the XOR of ``2^k * x`` over the set bits
    k of c, eight bytes at a time, in cache-sized column blocks."""
    d, s = shards.shape
    words = -(-s // 8)
    padded = np.zeros((d, words * 8), dtype=np.uint8)
    padded[:, :s] = shards
    data = padded.view(np.uint64)
    acc = np.zeros((len(rows), words), dtype=np.uint64)
    for lo in range(0, words, _BLOCK):
        out = acc[:, lo:lo + _BLOCK]
        for j in range(d):
            x = data[j, lo:lo + _BLOCK]
            for k in range(8):
                for i, row in enumerate(rows):
                    if row[j] >> k & 1:
                        out[i] ^= x
                if k < 7:
                    x = _times2(x)
    return acc.view(np.uint8)[:, :s].copy()


#: uint64 words per column block
_BLOCK = 8192


def split_parts(data: memoryview, d: int, chunk: int):
    """Yield each part's data shards ``[d, ceil(n/d)]``, zero-padded."""
    part = d * chunk
    for lo in range(0, len(data), part):
        n = min(part, len(data) - lo)
        s = -(-n // d)
        buf = np.zeros(d * s, dtype=np.uint8)
        buf[:n] = np.frombuffer(data[lo:lo + n], dtype=np.uint8)
        yield buf.reshape(d, s)


def expected_chunks(data, d: int, p: int, chunk: int):
    """Per part: ``(chunksize, [sha256 hex of d data + p parity chunks])``."""
    rows = parity_matrix(d, p)
    out = []
    for shards in split_parts(memoryview(data), d, chunk):
        parity = encode(shards, rows)
        out.append((shards.shape[1],
                    [hashlib.sha256(r).hexdigest()
                     for r in list(shards) + list(parity)]))
    return out
