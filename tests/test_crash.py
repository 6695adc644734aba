"""The crash-consistency harness (ISSUE 14): the filesystem seam, the
deterministic disk-fault injector, and the recovery verifier.

Four layers, mirroring the harness's own structure:

* seam mechanics — passthrough identity, recording op streams, the
  replayer's failure-model semantics (what survives a kill, a torn
  write, a power cut with and without directory fsync);
* the crash matrix — EVERY enumerated crash point of every
  storage-plane mutation (slab append/mark-dead/compact, chunk and
  metadata publication, the repair planner's rewrite shape) recovers
  invariant-clean, deterministically (same seed ⇒ same digest);
* scripted live faults — ENOSPC short writes truncate the slab tail
  (offset accounting never drifts), a failing fsync ABORTS compaction
  and metadata publication (never swallowed), stale publication temps
  are reaped by the next metadata write and by the GC walk;
* cluster recovery — crash images of one destination (including the
  journal-line-without-slab-bytes power-cut image slab.py documents)
  converge to Valid under ``scrub --once``.

Everything here is CPU-only and loop-local; the sanitize leg must stay
green (asyncio.run per case, no leaked tasks).
"""

from __future__ import annotations

import asyncio
import errno
import hashlib
import os
import subprocess
import sys
import threading
import time

import pytest

from chunky_bits_tpu.file.slab import SlabStore, SlabStoreError
from chunky_bits_tpu.sim import crash
from chunky_bits_tpu.utils import fsio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_provider():
    """Every test leaves the passthrough provider installed, whatever
    it broke."""
    yield
    fsio.install(None)


# ---- seam mechanics ----

def test_passthrough_provider_is_default_and_restores(tmp_path):
    assert fsio.active() is fsio.system_provider()
    recorder = fsio.RecordingFsProvider(str(tmp_path))
    previous = fsio.install(recorder)
    assert previous is fsio.system_provider()
    assert fsio.active() is recorder
    fsio.install(None)
    assert fsio.active() is fsio.system_provider()


def test_passthrough_open_is_the_builtin_file(tmp_path):
    # no wrapper on the hot path: production writes must cost one call
    path = str(tmp_path / "x")
    with fsio.open(path, "wb") as f:
        f.write(b"abc")
    with open(path, "rb") as f:
        assert f.read() == b"abc"
    import io

    with fsio.open(path, "ab") as f:
        assert isinstance(f, io.BufferedWriter)


def test_recording_captures_op_stream_and_scopes_to_root(tmp_path):
    root = tmp_path / "in"
    outside = tmp_path / "out"
    root.mkdir()
    outside.mkdir()
    recorder = fsio.RecordingFsProvider(str(root))
    fsio.install(recorder)
    try:
        with fsio.open(str(root / "a"), "wb") as f:
            f.write(b"payload")
            fsio.fsync(f)
        fsio.replace(str(root / "a"), str(root / "b"))
        fsio.fsync_dir(str(root))
        with fsio.open(str(outside / "c"), "wb") as f:
            f.write(b"elsewhere")
        with fsio.open(str(root / "b"), "rb") as f:
            assert f.read() == b"payload"  # reads not recorded
    finally:
        fsio.install(None)
    kinds = [(op.op, op.path) for op in recorder.ops]
    assert kinds == [
        ("open", "a"), ("write", "a"), ("flush", "a"), ("fsync", "a"),
        ("close", "a"), ("replace", "b"), ("fsync_dir", "."),
    ]
    assert recorder.ops[1].data == b"payload"
    assert recorder.ops[5].aux == "a"  # replace src


def _record_simple(root, body):
    os.makedirs(root, exist_ok=True)
    return crash.record_mutation(str(root), body)


def test_replayer_powercut_drops_unsynced_writes(tmp_path):
    root = tmp_path / "r"
    snap = tmp_path / "snap"
    root.mkdir()
    (root / "old").write_bytes(b"durable")
    import shutil

    shutil.copytree(root, snap)

    def body():
        with fsio.open(str(root / "new"), "wb") as f:
            f.write(b"unsynced")
            f.flush()

    ops = _record_simple(root, body)
    rep = crash.OpReplayer(str(snap))
    img = tmp_path / "img"
    # flush model: everything recorded survives
    rep.build(ops, len(ops), "flush", str(img))
    assert (img / "new").read_bytes() == b"unsynced"
    assert (img / "old").read_bytes() == b"durable"
    # powercut, keep-nothing mask: the dirent survives, the data died
    shutil.rmtree(img)
    rep.build(ops, len(ops), "powercut", str(img))
    assert (img / "new").read_bytes() == b""
    assert (img / "old").read_bytes() == b"durable"
    # powercut-meta with no fsync_dir anywhere: the file never existed
    shutil.rmtree(img)
    rep.build(ops, len(ops), "powercut-meta", str(img))
    assert not (img / "new").exists()


def test_replayer_fsync_and_dir_fsync_make_publication_durable(tmp_path):
    root = tmp_path / "r"
    snap = tmp_path / "snap"
    root.mkdir()
    (root / "t").write_bytes(b"old")
    import shutil

    shutil.copytree(root, snap)

    def body():
        with fsio.open(str(root / "t.tmp.1.00000000"), "wb") as f:
            f.write(b"new")
            fsio.fsync(f)
        fsio.replace(str(root / "t.tmp.1.00000000"), str(root / "t"))
        fsio.fsync_dir(str(root))

    ops = _record_simple(root, body)
    rep = crash.OpReplayer(str(snap))
    img = tmp_path / "img"
    # the full protocol survives the harshest model
    rep.build(ops, len(ops), "powercut-meta", str(img))
    assert (img / "t").read_bytes() == b"new"
    # crash BEFORE the dir fsync: the rename may be lost — old wins,
    # and the orphaned temp holds the fsync'd bytes
    shutil.rmtree(img)
    rep.build(ops, len(ops) - 1, "powercut-meta", str(img))
    assert (img / "t").read_bytes() == b"old"


def test_replayer_torn_write_cuts_final_write(tmp_path):
    root = tmp_path / "r"
    snap = tmp_path / "snap"
    root.mkdir()
    import shutil

    shutil.copytree(root, snap)

    def body():
        with fsio.open(str(root / "j"), "ab") as f:
            f.write(b"0123456789")
            f.flush()

    ops = _record_simple(root, body)
    write_k = next(i for i, op in enumerate(ops) if op.op == "write")
    rep = crash.OpReplayer(str(snap))
    img = tmp_path / "img"
    rep.build(ops, write_k + 1, "torn", str(img), torn=4)
    assert (img / "j").read_bytes() == b"0123"


# ---- the crash matrix: every point recovers, deterministically ----

@pytest.mark.parametrize("mutation", sorted(crash.MUTATIONS))
def test_crash_matrix_mutation_recovers_clean(tmp_path, mutation):
    result = crash.run_matrix(str(tmp_path), seed=0,
                              mutations=[mutation])
    assert result.verdicts, "no crash images enumerated"
    failed = result.failed()
    assert not failed, [v.to_obj() for v in failed[:5]]
    # the enumeration is real: multiple crash points and multiple
    # failure models per mutation
    assert result.ops_by_mutation[mutation] >= 3
    modes = {v.mode for v in result.verdicts}
    assert {"kill", "flush", "powercut", "powercut-meta"} <= modes


def test_crash_matrix_is_deterministic(tmp_path):
    picks = ["slab_append", "metadata_publish"]
    first = crash.run_matrix(str(tmp_path / "a"), seed=7,
                             mutations=picks)
    second = crash.run_matrix(str(tmp_path / "b"), seed=7,
                              mutations=picks)
    assert first.digest == second.digest
    assert [v.to_obj() for v in first.verdicts] \
        == [v.to_obj() for v in second.verdicts]


def test_crash_matrix_catches_a_dropped_dir_fsync(tmp_path, monkeypatch):
    """The harness is not vacuous: neuter the directory-fsync barrier
    (the satellite fix) and the completed-publication power-cut images
    MUST go red."""
    monkeypatch.setattr(fsio.FsProvider, "fsync_dir",
                        lambda self, path: None)
    monkeypatch.setattr(fsio.RecordingFsProvider, "fsync_dir",
                        lambda self, path: None, raising=False)
    result = crash.run_matrix(str(tmp_path), seed=0,
                              mutations=["metadata_publish"])
    failed = result.failed()
    assert failed, "neutered fsync_dir went undetected"
    assert any(v.mode == "powercut-meta" and "acknowledged" in
               " ".join(v.violations) for v in failed)


def test_chunk_publish_is_recorded_whole_from_one_worker(tmp_path):
    """A chunk publication runs as one worker call: the recorder sees
    its whole protocol (temp open, write, flush, close, rename onto the
    target), every op from one thread that is not the event loop's."""
    from chunky_bits_tpu.file.location import Location, is_publish_temp

    class ThreadNoting(fsio.RecordingFsProvider):
        def __init__(self, root):
            super().__init__(root)
            self.threads = set()

        def open(self, path, mode="r", **kwargs):
            self.threads.add(threading.get_ident())
            return super().open(path, mode, **kwargs)

        def replace(self, src, dst):
            self.threads.add(threading.get_ident())
            super().replace(src, dst)

    recorder = ThreadNoting(str(tmp_path))
    fsio.install(recorder)

    async def main():
        await Location.parse(str(tmp_path / "chunk")).write(b"payload")
        return threading.get_ident()

    loop_thread = asyncio.run(main())
    fsio.install(None)
    assert [op.op for op in recorder.ops] == [
        "open", "write", "flush", "close", "replace"]
    assert is_publish_temp(recorder.ops[0].path)
    assert recorder.ops[-1].path == "chunk"
    assert len(recorder.threads) == 1
    assert loop_thread not in recorder.threads


# ---- scripted live faults (the FaultyFsProvider satellite pins) ----

def _fresh_slab_with_chunks(root, n=2):
    store = SlabStore(str(root))
    expected = {}
    for i in range(n):
        payload = bytes([i]) * (300 + i)
        name = hashlib.sha256(payload).hexdigest()
        store.append(name, payload)
        expected[name] = payload
    return store, expected


def test_enospc_short_write_truncates_partial_tail(tmp_path):
    store, expected = _fresh_slab_with_chunks(tmp_path / "s")
    slab_file = os.path.join(store.root, store.slab_files()[-1])
    size_before = os.path.getsize(slab_file)
    fsio.install(fsio.FaultyFsProvider(
        "write", path_suffix=".slab", errno_code=errno.ENOSPC,
        short_bytes=17))
    try:
        with pytest.raises(OSError):
            store.append("a" * 64, b"x" * 4096)
    finally:
        fsio.install(None)
    # the partial 17-byte tail is truncated away: offsets never drift
    assert os.path.getsize(slab_file) == size_before
    # nothing journaled, store fully serviceable; the next append
    # lands exactly at the old EOF
    fresh = SlabStore(store.root)
    assert sorted(fresh.live_names()) == sorted(expected)
    payload = b"after-enospc"
    name = hashlib.sha256(payload).hexdigest()
    ext = fresh.append(name, payload)
    assert ext.offset == size_before
    assert fresh.pread(name) == payload
    for k, v in expected.items():
        assert fresh.pread(k) == v


def test_failed_fsync_aborts_compaction(tmp_path):
    store, expected = _fresh_slab_with_chunks(tmp_path / "s", n=3)
    store.mark_dead(sorted(expected)[0])
    with open(store.journal_path(), "rb") as f:
        journal_before = f.read()
    fsio.install(fsio.FaultyFsProvider("fsync"))
    try:
        with pytest.raises((OSError, SlabStoreError)):
            store.compact()
    finally:
        fsio.install(None)
    # the swap never happened: old journal authoritative, live chunks
    # all served, the dead extent still awaiting reclaim
    with open(store.journal_path(), "rb") as f:
        assert f.read() == journal_before
    fresh = SlabStore(store.root)
    for k in sorted(expected)[1:]:
        assert fresh.pread(k) == expected[k]
    assert fresh.dead_bytes() > 0
    # and with the fault gone, the same compaction succeeds
    fresh.compact()
    again = SlabStore(store.root)
    assert again.dead_bytes() == 0
    for k in sorted(expected)[1:]:
        assert again.pread(k) == expected[k]


def test_failed_dir_fsync_aborts_compaction_state_flip(tmp_path):
    store, expected = _fresh_slab_with_chunks(tmp_path / "s", n=3)
    store.mark_dead(sorted(expected)[0])
    fsio.install(fsio.FaultyFsProvider("fsync_dir"))
    try:
        with pytest.raises(OSError):
            store.compact()
    finally:
        fsio.install(None)
    # the rename may or may not be on disk — either way the cold
    # restart reads a complete journal and serves every live chunk
    fresh = SlabStore(store.root)
    for k in sorted(expected)[1:]:
        assert fresh.pread(k) == expected[k]


def test_failed_fsync_aborts_metadata_publication(tmp_path):
    from chunky_bits_tpu.cluster.metadata import MetadataPath
    from chunky_bits_tpu.errors import MetadataReadError

    meta = MetadataPath(str(tmp_path))
    asyncio.run(meta.write("obj", {"v": 1}))
    fsio.install(fsio.FaultyFsProvider("fsync"))
    try:
        with pytest.raises(MetadataReadError):
            asyncio.run(meta.write("obj", {"v": 2}))
    finally:
        fsio.install(None)
    # never swallowed-and-published: the old reference survives and
    # the staging temp was reaped on the error path
    assert asyncio.run(meta.read("obj")) == {"v": 1}
    from chunky_bits_tpu.file.location import is_publish_temp

    assert not [f for f in os.listdir(tmp_path) if is_publish_temp(f)]


def test_metadata_write_reaps_stale_temps_only(tmp_path):
    from chunky_bits_tpu.cluster.metadata import (
        STALE_TEMP_SECONDS,
        MetadataPath,
    )
    from chunky_bits_tpu.file.location import publish_temp_name

    asyncio.run(MetadataPath(str(tmp_path)).write("obj", {"v": 1}))
    stale = publish_temp_name(str(tmp_path / "obj"))
    fresh = publish_temp_name(str(tmp_path / "obj"))
    for path in (stale, fresh):
        with open(path, "w") as f:
            f.write("{}")
    old = time.time() - STALE_TEMP_SECONDS - 10
    os.utime(stale, (old, old))
    # the reap runs once per MetadataPath instance (per-write scans
    # would be O(dir) each — quadratic over a namespace); "next
    # write" means the next writer PROCESS, modeled by a new instance
    meta = MetadataPath(str(tmp_path))
    asyncio.run(meta.write("obj", {"v": 2}))
    # the crashed writer's leak is gone; the (possibly live) young
    # temp survives; the write itself landed
    assert not os.path.exists(stale)
    assert os.path.exists(fresh)
    assert asyncio.run(meta.read("obj")) == {"v": 2}
    # the same instance does not rescan: a later stale temp waits for
    # the next instance (amortized-cost contract)
    late = publish_temp_name(str(tmp_path / "obj"))
    with open(late, "w") as f:
        f.write("{}")
    os.utime(late, (old, old))
    asyncio.run(meta.write("obj", {"v": 3}))
    assert os.path.exists(late)
    asyncio.run(MetadataPath(str(tmp_path)).write("obj", {"v": 4}))
    assert not os.path.exists(late)


def test_gc_walk_reaps_stale_publish_temp(tmp_path):
    """The GC half of the stale-temp story: find-unused-hashes removes
    an aged publication temp from a hash dir (a writer killed between
    temp write and rename has no other reclamation path)."""
    import yaml

    disk = tmp_path / "disk0"
    disk.mkdir()
    (tmp_path / "metadata").mkdir()
    config = tmp_path / "cluster.yaml"
    config.write_text(yaml.safe_dump({
        "destinations": [{"location": str(disk)}],
        "metadata": {"type": "path", "format": "yaml",
                     "path": str(tmp_path / "metadata")},
        "profiles": {"default": {"data": 1, "parity": 1,
                                 "chunk_size": 12}},
    }))
    from chunky_bits_tpu.file.location import publish_temp_name

    temp = publish_temp_name(str(disk / ("sha256-" + "a" * 64)))
    with open(temp, "wb") as f:
        f.write(b"half-published")
    old = time.time() - 3600
    os.utime(temp, (old, old))
    r = subprocess.run(
        [sys.executable, "-m", "chunky_bits_tpu.cli",
         "find-unused-hashes", "--remove", f"{config}#.",
         "--", str(disk)],
        env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO,
        capture_output=True, timeout=120)
    assert r.returncode == 0, r.stderr.decode()[-500:]
    assert not os.path.exists(temp)
    assert b"Stale publish temp" in r.stderr


# ---- cluster recovery: crash image + scrub --once -> Valid ----

def test_scrub_once_converges_powercut_images(tmp_path):
    """The issue's named case end to end: the journal line survives
    the power cut, the slab bytes do not — scrub --once must detect
    the damage through the content-address gate and repair the node
    in place to a Valid namespace."""
    verdicts = crash.run_cluster_recovery(str(tmp_path / "w"), seed=0,
                                          points="smoke")
    assert verdicts, "no cluster crash images"
    # the smoke selection enumerates every writeback mask of the
    # completed ingest — including journal-without-bytes
    assert len(verdicts) >= 2
    failed = [v for v in verdicts if not v.ok]
    assert not failed, [v.to_obj() for v in failed]


# ---- sim fabric disk faults ----

def test_sim_node_torn_write_budget(tmp_path):
    from chunky_bits_tpu.sim.fabric import LatencyModel, SimFabric

    fabric = SimFabric("crashtest", 1, zones=("z",), seed=0,
                       latency=LatencyModel(median_ms=0.01))
    try:
        node = fabric.nodes["n0000"]
        node.faults.torn_put_bytes = 3
        node.faults.torn_put_remaining = 1

        async def drive():
            # a payload no longer than the torn prefix cannot tear and
            # must NOT burn the one-shot budget
            await node.write("tiny", b"ab")
            assert node.faults.torn_put_remaining == 1
            await node.write("c", b"0123456789")
            first = bytes(node.store["c"])
            await node.write("c", b"0123456789")
            return first, bytes(node.store["c"])

        torn, healed = asyncio.run(drive())
        assert torn == b"012"  # acked but torn
        assert healed == b"0123456789"  # budget spent: whole write
        assert node.torn_writes == 1
        assert node.stats()["torn_writes"] == 1
    finally:
        fabric.close()


@pytest.mark.slow
def test_disk_corruption_storm_scenario(tmp_path):
    """The scenario joins the PR-12 library: run it at unit scale (the
    bench --config 14 full suite re-proves it at N=100)."""
    from chunky_bits_tpu.sim.scenario import fresh_workdir, run_scenario

    result = run_scenario("disk_corruption_storm", nodes=12, seed=0,
                          workdir=fresh_workdir(str(tmp_path / "w")),
                          objects=6)
    assert result.ok(), result.to_obj()["verdicts"]
    assert result.verdicts["torn_writes_ridden_out"]
    assert result.verdicts["corruption_detected"]
