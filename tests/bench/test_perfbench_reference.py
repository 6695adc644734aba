"""The plain reference and the seeded payload.

The reference imports nothing of the program; here, and only here, it
is held against the program's own numpy codec, so a fault in either
shows."""

import hashlib
import sys

import numpy as np
import pytest

from benchutil import BENCH

sys.path.insert(0, BENCH)

import payload  # noqa: E402
import reference  # noqa: E402

GEOMETRIES = [(10, 4, 4096), (8, 3, 1000), (20, 6, 129), (3, 2, 7)]


@pytest.mark.parametrize("d,p,s", GEOMETRIES)
def test_reference_encode_equals_the_program(d, p, s):
    from chunky_bits_tpu.ops.backend import get_coder

    rng = np.random.default_rng(d * 100 + p)
    data = rng.integers(0, 256, (d, s), dtype=np.uint8)
    want = get_coder(d, p, "numpy").encode_batch(data[None])[0]
    assert np.array_equal(
        reference.encode(data, reference.parity_matrix(d, p)), want)


@pytest.mark.parametrize("d,p,s", GEOMETRIES[:3])
def test_reference_chunks_equal_a_stored_object(tmp_path, d, p, s):
    """A native-backend write's file reference: the reference gives the
    same part layout and every chunk's address."""
    import asyncio

    from chunky_bits_tpu.cluster import Cluster
    from chunky_bits_tpu.utils.aio import BytesReader

    import store

    config = {"profile": {"data": d, "parity": p, "chunk_size": 12},
              "destinations": d + p}
    cluster = Cluster.from_obj(store.cluster_obj(str(tmp_path), config,
                                                 "native"))
    body = payload.Payload(5, 3 * d * 4096).body(1, 2 * d * 4096 + s)
    ref = asyncio.run(cluster.write_file(
        "o/1", BytesReader(body.tobytes()), cluster.get_profile(None)))
    want = reference.expected_chunks(body, d, p, 4096)
    parts = [part.to_obj() for part in ref.parts]
    got = [(o["chunksize"], [c["sha256"] for c in o["data"] + o["parity"]])
           for o in parts]
    assert got == want


def test_payload_is_seeded_and_every_block_stamped():
    a = payload.Payload(2**33 + 1, 1 << 20)
    b = payload.Payload(2**33 + 1, 1 << 20)
    assert np.array_equal(a.body(3, 1 << 20), b.body(3, 1 << 20))
    assert not np.array_equal(a.body(3, 1 << 20),
                              payload.Payload(7, 1 << 20).body(3, 1 << 20))
    x, y = a.body(3, 1 << 20), a.body(4, 1 << 20)
    # no 4 KiB block of one object equals any block of another
    bx = {hashlib.sha256(x[i:i + 4096]).digest()
          for i in range(0, len(x), 4096)}
    by = {hashlib.sha256(y[i:i + 4096]).digest()
          for i in range(0, len(y), 4096)}
    assert not bx & by
    stamps = x.view(np.uint64)[::512]
    assert list(stamps[:3]) == [(3 << 32) | i for i in range(3)]


@pytest.mark.parametrize("size", [1 << 20, (1 << 20) - 13, 4096 * 3 + 5])
def test_body_built_in_a_reused_buffer_is_the_objects_bytes(size):
    """The load generator builds each body in its client's buffer; the
    check rebuilds it fresh: both give the same bytes, and nothing of a
    longer earlier body is left in the slice."""
    p = payload.Payload(2**31 + 9, 1 << 20)
    out = np.full(1 << 20, 0xAB, dtype=np.uint8)
    p.body_into(5, 1 << 20, out)
    got = p.body_into(6, size, out)
    assert got.base is out or got.base is out.base
    assert np.array_equal(got, p.body(6, size))
