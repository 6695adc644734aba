"""The writer's staging pool (``file/writer.py``, ``StagingPool``): a
staging block goes back for reuse only once its parts are encoded and
written, never after a failure or a cancellation, and a pool block's
stale bytes never reach a chunk."""

import asyncio
import random

import pytest

from chunky_bits_tpu.file import (
    FileReadBuilder,
    FileWriteBuilder,
    Location,
    LocationsDestination,
)
from chunky_bits_tpu.file import writer as writer_mod
from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.utils import aio

D, P, CHUNK, STAGE = 3, 2, 1024, 4
PART = D * CHUNK
SHAPE = (STAGE, D, CHUNK)


def body(n: int, seed: int) -> bytes:
    return random.Random(seed).randbytes(n)


@pytest.fixture
def pool(monkeypatch):
    """A fresh staging pool, and a fresh registry the writer counts
    into."""
    fresh = writer_mod.StagingPool()
    monkeypatch.setattr(writer_mod, "_STAGING", fresh)
    monkeypatch.setattr(obs_metrics, "_REGISTRY", None)
    monkeypatch.setattr(obs_metrics, "_EVENT_FAMILIES", {})
    return fresh


def taken() -> dict:
    """``cb_writer_staging_blocks_total`` by source."""
    samples = obs_metrics.get_registry().counter(
        "cb_writer_staging_blocks_total", labels=("source",))._samples()
    return {s["labels"]["source"]: s["value"] for s in samples}


@pytest.fixture
def disks(tmp_path):
    out = []
    for i in range(D + P):
        path = tmp_path / f"disk{i}"
        path.mkdir()
        out.append(Location.parse(str(path)))
    return out


class SlowDestination:
    """Every chunk write waits ``delay`` s before it reads its payload;
    past ``fail_after`` writes, a write raises."""

    def __init__(self, locations, delay=0.0, fail_after=None):
        self.inner = LocationsDestination(locations)
        self.delay = delay
        self.fail_after = fail_after
        self.writes = 0
        self.started = asyncio.Event()

    def get_writers(self, count):
        return [_SlowWriter(self, w) for w in self.inner.get_writers(count)]

    def get_context(self):
        return self.inner.get_context()


class _SlowWriter:
    def __init__(self, dest, inner):
        self.dest = dest
        self.inner = inner

    async def write_shard(self, hash_, data):
        self.dest.started.set()
        await asyncio.sleep(self.dest.delay)
        self.dest.writes += 1
        if self.dest.fail_after is not None \
                and self.dest.writes > self.dest.fail_after:
            raise OSError("disk went away")
        return await self.inner.write_shard(hash_, data)


def builder(destination, concurrency=12):
    return (FileWriteBuilder()
            .with_destination(destination)
            .with_chunk_size(CHUNK)
            .with_data_chunks(D)
            .with_parity_chunks(P)
            .with_batch_parts(8)
            .with_stage_parts(STAGE)
            .with_concurrency(concurrency))


def layout(ref):
    return (ref.length, [(part.chunksize, [c.hash for c in part.all_chunks()])
                         for part in ref.parts])


async def check_chunks(ref, payload):
    """Every chunk file hashes to its address, and the object reads back
    byte-exact."""
    for part in ref.parts:
        for chunk in part.all_chunks():
            for loc in chunk.locations:
                with open(loc.target, "rb") as f:
                    assert chunk.hash.verify(f.read()), loc.target
    assert await FileReadBuilder(ref).read_all() == payload


@pytest.mark.parametrize("tail", [1000, PART - 1, PART])
def test_second_write_reuses_the_block_and_matches_a_fresh_one(
        pool, disks, tail):
    """Two writes of one geometry in turn: the second takes only blocks
    the first gave back, and its reference is that of a write into
    fresh blocks.  Its tail part (short, near-full and zero-padded, or
    full) lies over the first body's bytes in the block.  A body of
    whole blocks takes one more to find its end."""
    first = body(STAGE * PART, seed=1)
    second = body(3 * PART + tail, seed=2)

    async def main():
        dest = LocationsDestination(disks)
        await builder(dest).write(aio.BytesReader(first))
        assert taken() == {"fresh": 2}
        ref = await builder(dest).write(aio.BytesReader(second))
        assert taken() == {"fresh": 2, "reused": 1 + (tail == PART)}
        assert pool.stats() == {SHAPE: {"free": 2, "out": 0, "peak": 2}}
        writer_mod._STAGING = writer_mod.StagingPool()
        plain = await builder(dest).write(aio.BytesReader(second))
        assert layout(ref) == layout(plain)
        await check_chunks(ref, second)

    asyncio.run(main())


def test_concurrent_writes_keep_blocks_until_their_chunks_are_written(
        pool, disks):
    """Eight writes at once, every chunk write delayed, so blocks stay in
    flight while other writes take theirs: every chunk hashes to its
    address and every object reads back exactly."""
    payloads = [body(5 * STAGE * PART + 77 * i, seed=10 + i)
                for i in range(8)]

    async def main():
        dest = SlowDestination(disks, delay=0.002)
        refs = await asyncio.gather(*[
            builder(dest).write(aio.BytesReader(b)) for b in payloads])
        for ref, payload in zip(refs, payloads):
            await check_chunks(ref, payload)
        counts = taken()
        assert counts.get("reused", 0) > 0
        assert sum(counts.values()) == 8 * 6

    asyncio.run(main())


@pytest.mark.parametrize("how", ["destination fails", "cancelled writing",
                                 "cancelled reading"])
def test_a_failed_or_cancelled_write_never_returns_its_block(
        pool, disks, how):
    """A write that fails or is cancelled mid-stream drops its block
    (a thread may still read or fill it); the next write takes a fresh
    one and is exact."""
    payload = body(3 * PART + 5, seed=3)

    class StallingReader:
        """Serves two parts, then waits for ever."""

        def __init__(self):
            self.inner = aio.BytesReader(payload[:2 * PART])
            self.stalled = asyncio.Event()

        async def read(self, n=-1):
            data = await self.inner.read(n)
            if not data:
                self.stalled.set()
                await asyncio.Event().wait()
            return data

    async def main():
        if how == "destination fails":
            dest = SlowDestination(disks, fail_after=4)
            with pytest.raises(OSError, match="disk went away"):
                await builder(dest).write(aio.BytesReader(payload))
        else:
            dest = SlowDestination(disks, delay=30)
            reader = StallingReader() if how == "cancelled reading" \
                else aio.BytesReader(payload)
            task = asyncio.create_task(builder(dest).write(reader))
            await asyncio.wait_for(
                (reader.stalled if how == "cancelled reading"
                 else dest.started).wait(), 10)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
        assert pool.stats() == {SHAPE: {"free": 0, "out": 0, "peak": 1}}
        ref = await builder(LocationsDestination(disks)).write(
            aio.BytesReader(payload))
        assert taken() == {"fresh": 2}
        await check_chunks(ref, payload)

    asyncio.run(main())


@pytest.mark.parametrize("writers", [2, 8])
def test_pool_keeps_no_more_blocks_than_were_out_at_once(
        pool, disks, writers):
    """After concurrent writes end, each shape's free list holds the
    blocks it made, which is the most that were out at once: the pool
    keeps no more staging memory than the busiest moment held."""
    other = (2, D, CHUNK)

    async def main():
        dest = SlowDestination(disks, delay=0.001)
        await asyncio.gather(
            *[builder(dest).write(aio.BytesReader(
                body(3 * STAGE * PART + 9, seed=20 + i)))
              for i in range(writers)],
            *[builder(dest).with_stage_parts(2).write(aio.BytesReader(
                body(5 * PART, seed=40 + i))) for i in range(writers)])

    asyncio.run(main())
    stats = pool.stats()
    assert set(stats) == {SHAPE, other}
    for shape, s in stats.items():
        assert s["out"] == 0, shape
        assert 1 <= s["free"] == s["peak"], (shape, s)
    assert taken()["fresh"] == sum(s["peak"] for s in stats.values())
