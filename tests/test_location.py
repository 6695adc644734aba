"""Location substrate tests (mirror of reference tests/location.rs):
FS + HTTP read, subfile write, streaming, conflict policy, ranges,
parse/display."""

import asyncio
import errno
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from chunky_bits_tpu.errors import (
    HttpStatusError,
    LocationError,
    LocationParseError,
    WriteToRangeError,
)
from chunky_bits_tpu.file.hashing import AnyHash
from chunky_bits_tpu.file.location import (
    IGNORE,
    Location,
    LocationContext,
    Range,
    is_publish_temp,
)
from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.utils import aio, fsio
from tests.http_node import FakeHttpNode


def test_parse_display_roundtrip():
    cases = [
        "/tmp/some/path",
        "relative/path",
        "http://example.com/data",
        "https://example.com/data",
        "(5,10)/tmp/file",
        "(5,)/tmp/file",
        "(0,0128)/tmp/file",
        "(7,12)http://example.com/x",
    ]
    for s in cases:
        loc = Location.parse(s)
        assert str(loc) == s, s


def test_parse_range_semantics():
    loc = Location.parse("(5,10)/tmp/file")
    assert loc.range == Range(5, 10, False)
    loc = Location.parse("(5,)/tmp/file")
    assert loc.range == Range(5, None, False)
    loc = Location.parse("(0,0128)/tmp/file")
    assert loc.range == Range(0, 128, True)
    # no valid prefix -> the parens belong to the path
    loc = Location.parse("(x,y)/tmp/file")
    assert loc.target == "(x,y)/tmp/file"


def test_parse_file_url():
    loc = Location.parse("file:///tmp/abc")
    assert loc.is_local() and loc.target == "/tmp/abc"


def test_parse_errors():
    with pytest.raises(LocationParseError):
        Location.parse("")
    with pytest.raises(LocationParseError):
        Location.http("ftp://example.com/x")


def test_child_and_parent():
    base = Location.parse("/tmp/dir")
    child = base.child("abc")
    assert str(child) == "/tmp/dir/abc"
    assert child.is_child_of(base)
    assert base.is_parent_of(child)
    hbase = Location.parse("http://example.com/data")
    hchild = hbase.child("abc")
    assert str(hchild) == "http://example.com/data/abc"
    assert hchild.is_child_of(hbase)
    assert not hchild.is_child_of(base)


def test_fs_read(tmp_path):
    # the reference uses /bin/sh as an always-present file
    # (tests/location.rs:101-107); a tempfile is equivalent and hermetic
    path = tmp_path / "content"
    path.write_bytes(b"some test content")

    async def main():
        loc = Location.parse(str(path))
        assert await loc.read() == b"some test content"
        assert await loc.file_exists()
        assert await loc.file_len() == 17

    asyncio.run(main())


def test_fs_read_missing(tmp_path):
    async def main():
        loc = Location.parse(str(tmp_path / "missing"))
        with pytest.raises(LocationError):
            await loc.read()
        assert not await loc.file_exists()

    asyncio.run(main())


def test_fs_write_subfile_and_delete(tmp_path):
    async def main():
        base = Location.parse(str(tmp_path))
        hash_ = AnyHash.from_buf(b"shard bytes")
        child = await base.write_subfile(str(hash_), b"shard bytes")
        assert child.is_child_of(base)
        assert await child.read() == b"shard bytes"
        locs = await base.write_shard(hash_, b"shard bytes")
        assert locs == [child]
        await child.delete()
        assert not await child.file_exists()

    asyncio.run(main())


def test_fs_range_reads(tmp_path):
    path = tmp_path / "ranged"
    path.write_bytes(bytes(range(100)))

    async def main():
        loc = Location.local(str(path), Range(10, 20, False))
        assert await loc.read() == bytes(range(10, 30))
        # extend_zeros pads reads past EOF (location.rs:127-129)
        loc = Location.local(str(path), Range(90, 20, True))
        data = await loc.read()
        assert data == bytes(range(90, 100)) + b"\0" * 10
        # open-ended
        loc = Location.local(str(path), Range(95, None, False))
        assert await loc.read() == bytes(range(95, 100))
        # writes to ranged locations are rejected
        with pytest.raises(WriteToRangeError):
            await loc.write(b"x")

    asyncio.run(main())


def test_fs_conflict_policy(tmp_path):
    path = tmp_path / "conflict"

    async def main():
        loc = Location.parse(str(path))
        await loc.write(b"first")
        ignore_cx = LocationContext(on_conflict=IGNORE)
        await loc.write(b"second", ignore_cx)
        assert await loc.read() == b"first"  # ignored
        await loc.write(b"third")  # default overwrite
        assert await loc.read() == b"third"

    asyncio.run(main())


def test_fs_streaming(tmp_path):
    src = tmp_path / "src"
    dst = tmp_path / "dst"
    src.write_bytes(os.urandom(3 << 20))

    async def main():
        sloc = Location.parse(str(src))
        dloc = Location.parse(str(dst))
        reader = await sloc.reader()
        n = await dloc.write_from_reader(reader)
        await aio.close_reader(reader)
        assert n == 3 << 20
        assert dst.read_bytes() == src.read_bytes()

    asyncio.run(main())


def test_http_full_cycle():
    async def main():
        node = await FakeHttpNode().start()
        cx = LocationContext()
        try:
            base = Location.parse(node.url + "/data")
            hash_ = AnyHash.from_buf(b"http shard")
            child = await base.write_subfile(str(hash_), b"http shard", cx)
            assert str(child) == f"{node.url}/data%2F{hash_}" or \
                child.is_child_of(base)
            assert await child.read(cx) == b"http shard"
            assert await child.file_exists(cx)
            assert await child.file_len(cx) == len(b"http shard")
            # conflict ignore
            icx = LocationContext(on_conflict=IGNORE)
            icx._sessions = cx._sessions
            await child.write(b"changed", icx)
            assert await child.read(cx) == b"http shard"
            # overwrite
            await child.write(b"changed", cx)
            assert await child.read(cx) == b"changed"
            # range read
            rloc = child.with_range(Range(2, 3, False))
            assert await rloc.read(cx) == b"ang"
            # delete
            await child.delete(cx)
            with pytest.raises(HttpStatusError):
                await child.read(cx)
            # streaming put
            dloc = Location.parse(node.url + "/streamed")
            n = await dloc.write_from_reader(
                aio.BytesReader(b"x" * 100000), cx)
            assert n == 100000
            assert await dloc.read(cx) == b"x" * 100000
        finally:
            await cx.aclose()
            await node.stop()

    asyncio.run(main())


def test_http_put_failure_raises():
    """A failed PUT (e.g. disk full) must surface, never report success."""
    async def main():
        node = await FakeHttpNode().start()
        cx = LocationContext()
        try:
            loc = Location.parse(node.url + "/fail/x")
            with pytest.raises(HttpStatusError):
                await loc.write(b"data", cx)
            with pytest.raises(HttpStatusError):
                await loc.write_from_reader(aio.BytesReader(b"data"), cx)
        finally:
            await cx.aclose()
            await node.stop()

    asyncio.run(main())


def test_http_missing_404():
    async def main():
        node = await FakeHttpNode().start()
        cx = LocationContext()
        try:
            loc = Location.parse(node.url + "/nope")
            with pytest.raises(HttpStatusError):
                await loc.read(cx)
            assert not await loc.file_exists(cx)
        finally:
            await cx.aclose()
            await node.stop()

    asyncio.run(main())


def test_streaming_profiler_hooks(tmp_path):
    """Streaming reader/writer paths emit one profiler entry per stream —
    the hooks the reference leaves as TODO (src/file/location.rs:119,255)."""
    from chunky_bits_tpu.file.profiler import new_profiler
    from chunky_bits_tpu.utils import aio as aio_utils

    payload = os.urandom(100000)
    src = tmp_path / "src.bin"
    src.write_bytes(payload)

    async def main():
        profiler, reporter = new_profiler()
        cx = LocationContext(profiler=profiler)

        # streaming read to EOF: one successful entry, full byte count
        reader = await Location.parse(str(src)).reader(cx)
        total = 0
        while True:
            data = await reader.read(8192)
            if not data:
                break
            total += len(data)
        await aio_utils.close_reader(reader)
        assert total == len(payload)

        # early close: entry logged with partial count, not dropped
        reader = await Location.parse(str(src)).reader(cx)
        first = await reader.read(4096)
        await aio_utils.close_reader(reader)
        assert len(first) == 4096

        # streaming write: one successful write entry
        dst = Location.parse(str(tmp_path / "dst.bin"))
        await dst.write_from_reader(aio_utils.BytesReader(payload), cx)

        # open failure logs a failed read entry
        with pytest.raises(LocationError):
            await Location.parse(str(tmp_path / "missing.bin")).reader(cx)

        report = reporter.profile()
        reads = [e for e in report.entries if e.kind == "read"]
        writes = [e for e in report.entries if e.kind == "write"]
        assert len(reads) == 3
        assert [e.ok for e in reads] == [True, True, False]
        assert reads[0].length == len(payload)
        assert reads[1].length == 4096
        assert len(writes) == 1
        assert writes[0].ok and writes[0].length == len(payload)

    asyncio.run(main())


def test_https_only_refuses_plain_http():
    """With the https_only tunable set, every network verb refuses a
    plain-http location (the reference builds its whole client https-only,
    src/cluster/tunables.rs:25-32)."""
    async def main():
        node = await FakeHttpNode().start()
        open_cx = LocationContext()
        cx = LocationContext(https_only=True)
        cx._sessions = open_cx._sessions
        try:
            loc = Location.parse(node.url + "/sec")
            await loc.write(b"payload", open_cx)  # plain context still works
            for op in (
                loc.read(cx),
                loc.reader(cx),
                loc.write(b"x", cx),
                loc.write_from_reader(aio.BytesReader(b"x"), cx),
                loc.delete(cx),
                loc.file_exists(cx),
                loc.file_len(cx),
            ):
                with pytest.raises(LocationError, match="https_only"):
                    await op
            # nothing was modified through the refusing context
            assert await loc.read(open_cx) == b"payload"
            # local locations are unaffected by https_only
        finally:
            await open_cx.aclose()
            await node.stop()

    asyncio.run(main())


def test_https_only_leaves_local_alone(tmp_path):
    f = tmp_path / "f"
    f.write_bytes(b"local")

    async def main():
        cx = LocationContext(https_only=True)
        assert await Location.parse(str(f)).read(cx) == b"local"

    asyncio.run(main())


def test_https_only_refuses_redirect_hops():
    """Under https_only a redirect answer is refused (mutating verbs run
    with redirects disabled), and a GET whose hop chain touched plain
    http is refused before the body is consumed.  Stub responses stand in
    for a TLS endpoint, which the test node cannot provide."""
    from types import SimpleNamespace
    from urllib.parse import urlsplit

    cx = LocationContext(https_only=True)
    loc = Location.http("https://node.example/chunk")

    class StubUrl:
        def __init__(self, url):
            self.scheme = urlsplit(url).scheme
            self._url = url

        def __str__(self):
            return self._url

    def resp(status, url, history=()):
        return SimpleNamespace(
            status=status,
            url=StubUrl(url),
            history=tuple(
                SimpleNamespace(url=StubUrl(u)) for u in history),
            release=lambda: None,
        )

    with pytest.raises(LocationError, match="refusing redirect"):
        loc._check_redirect(cx, resp(302, "https://node.example/chunk"))
    with pytest.raises(LocationError, match="plain http"):
        loc._check_response_hops(
            cx, resp(200, "http://node.example/chunk",
                     history=["https://node.example/chunk"]))
    # all-https chains pass both checks
    loc._check_redirect(cx, resp(200, "https://node.example/chunk"))
    loc._check_response_hops(
        cx, resp(200, "https://node2.example/chunk",
                 history=["https://node.example/chunk"]))
    # without the tunable both checks are no-ops
    open_cx = LocationContext()
    loc._check_redirect(open_cx, resp(302, "https://node.example/chunk"))


def test_plain_context_follows_redirects():
    """Without https_only, redirects keep working end-to-end."""
    async def main():
        node = await FakeHttpNode().start()
        cx = LocationContext()
        try:
            real = Location.parse(node.url + "/real")
            await real.write(b"payload", cx)
            via = Location.parse(node.url + "/redir/real")
            assert await via.read(cx) == b"payload"
        finally:
            await cx.aclose()
            await node.stop()

    asyncio.run(main())


def test_read_view_semantics(tmp_path, monkeypatch):
    """read_view serves zero-copy page-cache views for local (ranged)
    reads inside the file, and declines exactly where the generic path
    owns the semantics (past-EOF ranges, profiler, opt-out env,
    non-local)."""
    monkeypatch.delenv("CHUNKY_BITS_TPU_NO_MMAP", raising=False)
    data = bytes(range(256)) * 8  # 2048 bytes
    path = tmp_path / "chunk"
    path.write_bytes(data)

    async def main():
        loc = Location.parse(str(path))
        view = await loc.read_view()
        assert view is not None and bytes(view) == data
        # ranged, fully inside the file (incl. extend_zeros interior)
        ranged = Location.parse(f"(64,128){path}")
        view = await ranged.read_view()
        assert bytes(view) == data[64:192]
        assert bytes(view) == await ranged.read()
        # range reaching past EOF: generic path owns short/zero semantics
        over = Location.parse(f"(2000,128){path}")
        assert await over.read_view() is None
        # profiler active: generic read must be observed
        from chunky_bits_tpu.file import new_profiler
        profiler, reporter = new_profiler()
        cx = LocationContext(profiler=profiler)
        assert await loc.read_view(cx) is None
        # opt-out env
        monkeypatch.setenv("CHUNKY_BITS_TPU_NO_MMAP", "1")
        assert await loc.read_view() is None
        monkeypatch.delenv("CHUNKY_BITS_TPU_NO_MMAP")
        # missing file: None, not an exception
        assert await Location.parse(
            str(tmp_path / "absent")).read_view() is None

    asyncio.run(main())


def test_atomic_write_preserves_held_views(tmp_path, monkeypatch):
    """Local writes publish via temp+rename: a view taken before an
    overwrite keeps serving the old inode's bytes (never SIGBUS, never
    torn), the path serves the new content, and no temp files leak."""
    monkeypatch.delenv("CHUNKY_BITS_TPU_NO_MMAP", raising=False)
    path = tmp_path / "chunk"
    old, new = b"A" * 4096, b"B" * 4096

    async def main():
        loc = Location.parse(str(path))
        await loc.write(old)
        view = await loc.read_view()
        assert bytes(view) == old
        await loc.write(new)  # default policy: overwrite
        # the held view still reads the old, unlinked inode
        assert bytes(view) == old
        assert await loc.read() == new
        assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []

    asyncio.run(main())


def test_atomic_write_edge_cases(tmp_path):
    """Symlinked targets are written through (link preserved), file
    modes survive replacement, negative ranges decline the view path,
    and streaming local writes publish atomically."""

    async def main():
        # symlink: write through, don't replace the link node
        real = tmp_path / "real.bin"
        real.write_bytes(b"old")
        link = tmp_path / "link.bin"
        link.symlink_to(real)
        await Location.parse(str(link)).write(b"through-the-link")
        assert link.is_symlink()
        assert real.read_bytes() == b"through-the-link"
        # mode preserved across replace
        secret = tmp_path / "secret.bin"
        secret.write_bytes(b"v1")
        os.chmod(secret, 0o600)
        await Location.parse(str(secret)).write(b"v2")
        assert os.stat(secret).st_mode & 0o777 == 0o600
        assert secret.read_bytes() == b"v2"
        # negative range: view path declines (generic read errors)
        data = bytes(range(64))
        f = tmp_path / "f.bin"
        f.write_bytes(data)
        neg = Location.parse(f"(-10,5){f}")
        assert await neg.read_view() is None
        # streaming write publishes atomically: failed stream leaves
        # the previous content intact, success leaves no temp files
        class FailingReader:
            async def read(self, n: int = -1) -> bytes:
                raise OSError("stream died")

        out = tmp_path / "out.bin"
        out.write_bytes(b"previous")
        with pytest.raises(LocationError, match="stream died"):
            await Location.parse(str(out)).write_from_reader(
                FailingReader())
        assert out.read_bytes() == b"previous"
        await Location.parse(str(out)).write_from_reader(
            aio.BytesReader(b"streamed"))
        assert out.read_bytes() == b"streamed"
        assert [x for x in os.listdir(tmp_path) if ".tmp." in x] == []

    asyncio.run(main())


# ---- local chunk publication: one worker call, off the loop ----


class _ThreadRecordingFs(fsio.FsProvider):
    """Passthrough seam that notes the thread of every call."""

    def __init__(self):
        self.calls = []

    def open(self, path, mode="r", **kwargs):
        self.calls.append(("open", threading.get_ident()))
        return super().open(path, mode, **kwargs)

    def replace(self, src, dst):
        self.calls.append(("replace", threading.get_ident()))
        super().replace(src, dst)

    def unlink(self, path):
        self.calls.append(("unlink", threading.get_ident()))
        super().unlink(path)


@pytest.fixture
def seam():
    """Installs a provider for the test and always restores the
    passthrough."""
    yield fsio.install
    fsio.install(None)


class _CountingExecutor(ThreadPoolExecutor):
    def __init__(self):
        super().__init__(max_workers=4)
        self.submits = 0

    def submit(self, *args, **kwargs):
        self.submits += 1
        return super().submit(*args, **kwargs)


def _publish_counts():
    samples = obs_metrics.get_registry().counter(
        "cb_chunk_publish_total", labels=("outcome",))._samples()
    return {s["labels"]["outcome"]: s["value"] for s in samples}


@pytest.fixture
def publish_counter(monkeypatch):
    """Reads ``cb_chunk_publish_total`` by outcome from the registry the
    test's records land in."""
    monkeypatch.setattr(obs_metrics, "_EVENT_FAMILIES", {})
    return _publish_counts


def _temps(path):
    return [f for f in os.listdir(path) if ".tmp." in f]


@pytest.mark.parametrize("how", ["write", "write-ignore", "stream"])
def test_local_publication_keeps_filesystem_calls_off_the_loop(
        tmp_path, seam, how):
    """No seam call of a local publication (temp open, rename, reap)
    runs on the event loop's thread, the rename included."""
    rec = _ThreadRecordingFs()
    seam(rec)
    target = tmp_path / "chunk"
    target.write_bytes(b"old")

    async def main():
        loc = Location.parse(str(target))
        if how == "stream":
            await loc.write_from_reader(aio.BytesReader(b"new"))
        else:
            cx = LocationContext(
                on_conflict=IGNORE if how == "write-ignore" else "overwrite")
            await loc.write(b"new", cx)
            await Location.parse(str(tmp_path / "fresh")).write(b"x", cx)
        return threading.get_ident()

    loop_thread = asyncio.run(main())
    ops = [op for op, _ in rec.calls]
    assert "open" in ops and "replace" in ops, ops
    assert all(t != loop_thread for _, t in rec.calls), rec.calls
    assert target.read_bytes() == (b"old" if how == "write-ignore"
                                   else b"new")
    assert _temps(tmp_path) == []


@pytest.mark.parametrize("existing", [False, True])
def test_local_write_is_one_executor_call(tmp_path, seam, existing):
    """A local chunk write makes exactly one executor call; under IGNORE
    an existing target costs that one call and writes nothing."""
    rec = _ThreadRecordingFs()
    seam(rec)
    target = tmp_path / "chunk"
    if existing:
        target.write_bytes(b"there")
    pool = _CountingExecutor()

    async def main():
        asyncio.get_running_loop().set_default_executor(pool)
        await Location.parse(str(target)).write(
            b"payload", LocationContext(on_conflict=IGNORE))

    asyncio.run(main())
    assert pool.submits == 1
    if existing:
        assert rec.calls == []
        assert target.read_bytes() == b"there"
    else:
        assert [op for op, _ in rec.calls] == ["open", "replace"]
        assert target.read_bytes() == b"payload"


class _BlockingWriteFs(fsio.FsProvider):
    """Holds the temp write until released; optionally fails it."""

    def __init__(self, fail):
        self.fail = fail
        self.in_write = threading.Event()
        self.release = threading.Event()

    def open(self, path, mode="r", **kwargs):
        real = super().open(path, mode, **kwargs)
        if "w" not in mode:
            return real
        provider = self

        class _File:
            def write(self, data):
                provider.in_write.set()
                provider.release.wait(10)
                if provider.fail:
                    raise OSError(errno.EIO, "injected")
                return real.write(data)

            def flush(self):
                real.flush()

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                real.close()

        return _File()


@pytest.mark.parametrize("fail", [False, True])
def test_cancelled_publication_completes_or_reaps_its_temp(
        tmp_path, seam, fail):
    """Cancelling the writer while its worker is inside the temp write:
    the CancelledError reaches the caller at once, and once the worker
    is done the target is whole (or absent, when the write failed) with
    no temp file left."""
    fs = _BlockingWriteFs(fail)
    seam(fs)
    target = tmp_path / "chunk"
    data = os.urandom(4096)

    async def main():
        task = asyncio.create_task(Location.parse(str(target)).write(data))
        assert await asyncio.to_thread(fs.in_write.wait, 10)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert not fs.release.is_set()  # the worker is still writing
        fs.release.set()

    # asyncio.run returns once the default executor's threads are done
    asyncio.run(main())
    if fail:
        assert not target.exists()
    else:
        assert target.read_bytes() == data
    assert _temps(tmp_path) == []


class _RefuseTempsFs(fsio.FsProvider):
    """A directory that refuses new files but not writes in place."""

    def open(self, path, mode="r", **kwargs):
        if is_publish_temp(path):
            raise PermissionError(errno.EACCES, "refused", path)
        return super().open(path, mode, **kwargs)


def test_publication_outcomes_are_counted(tmp_path, seam, publish_counter):
    """``cb_chunk_publish_total`` counts each outcome: a fresh chunk is
    renamed into place, an existing one skipped under IGNORE, a symlink,
    a special file and a directory refusing the temp are written
    directly — each as before, with no temp left behind."""
    ignore = LocationContext(on_conflict=IGNORE)
    real = tmp_path / "real.bin"
    real.write_bytes(b"old")
    link = tmp_path / "link.bin"
    link.symlink_to(real)
    dangling = tmp_path / "dangling.bin"
    dangling.symlink_to(tmp_path / "absent.bin")
    refused = tmp_path / "refused.bin"
    refused.write_bytes(b"old")

    async def main():
        await Location.parse(str(tmp_path / "a")).write(b"a")
        await Location.parse(str(tmp_path / "a")).write(b"b", ignore)
        await Location.parse(str(link)).write(b"through")
        # a dangling link does not exist: IGNORE writes through it
        await Location.parse(str(dangling)).write(b"made", ignore)
        await Location.parse(os.devnull).write(b"x")
        seam(_RefuseTempsFs())
        await Location.parse(str(refused)).write(b"in place")
        seam(None)

    before = publish_counter()
    asyncio.run(main())
    after = publish_counter()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("renamed", "skipped", "direct")}
    assert delta == {"renamed": 1, "skipped": 1, "direct": 4}
    assert (tmp_path / "a").read_bytes() == b"a"
    assert link.is_symlink() and real.read_bytes() == b"through"
    assert dangling.is_symlink()
    assert (tmp_path / "absent.bin").read_bytes() == b"made"
    assert refused.read_bytes() == b"in place"
    assert _temps(tmp_path) == []
