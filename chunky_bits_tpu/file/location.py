"""Uniform storage addressing: local filesystem paths and HTTP endpoints.

Mirrors the reference's ``Location`` (src/file/location.rs:61-68): an address
is ``Local{path, range}`` or ``Http{url, range}``, serialized as a plain
string with an optional ``(start,len)`` range prefix
(location.rs:550-603).  Supported verbs: read (with Range/zero-extension),
write (with conflict policy), streaming write, subfile write
(content-addressed children), delete, exists, len.

The async substrate is asyncio + aiohttp (the reference's tokio + reqwest
role); filesystem calls hop to threads.  One deviation, documented: the
reference's HTTP ``file_len`` is ``todo!()`` (location.rs:394) — here it
reads Content-Length from a HEAD response.
"""

from __future__ import annotations

import asyncio
import errno
import io
import os
import re
import stat
from dataclasses import dataclass, field, replace
from typing import Optional
from urllib.parse import quote, urlsplit, urlunsplit

#: the clock seam (cluster/clock.py is the canonical surface; the
#: implementation lives in utils/ so file/ modules can import it
#: without triggering the cluster package __init__ — import-cycle
#: hygiene, same as errors.py).  Every latency the health scoreboard
#: and profiler see comes off this clock, so the simulator's virtual
#: timebase flows through unchanged.
from chunky_bits_tpu.utils import clock as _clock
from chunky_bits_tpu.utils import fsio as _fsio

from chunky_bits_tpu.errors import (
    HttpStatusError,
    LocationError,
    LocationParseError,
    ShardError,
    WriteToRangeError,
)
from chunky_bits_tpu.file.hashing import AnyHash
from chunky_bits_tpu.file.profiler import Profiler
from chunky_bits_tpu.obs import metrics as obs_metrics
from chunky_bits_tpu.obs import tracing as obs_tracing
from chunky_bits_tpu.utils import aio

OVERWRITE = "overwrite"
IGNORE = "ignore"


@dataclass(frozen=True)
class Range:
    """Byte range view over a location (src/file/location.rs:550-603)."""

    start: int = 0
    length: Optional[int] = None
    extend_zeros: bool = False

    def is_specified(self) -> bool:
        return self.start != 0 or self.length is not None

    def __str__(self) -> str:
        if self.length is not None and not self.extend_zeros:
            return f"({self.start},{self.length})"
        if self.length is not None and self.extend_zeros:
            return f"({self.start},0{self.length})"
        return f"({self.start},)"

    @staticmethod
    def from_str_prefix(s: str) -> tuple["Range", str]:
        """Split a leading ``(start,len)`` prefix off a location string;
        a length with a leading ``0`` marks zero-extension
        (location.rs:581-602)."""
        if s.startswith("("):
            inner, sep, suffix = s[1:].partition(")")
            if sep:
                left, comma, right = inner.partition(",")
                if comma:
                    try:
                        start = int(left)
                        length = int(right) if right else None
                    except ValueError:
                        return Range(), s
                    if right and (not right.lstrip("-").isdigit()):
                        return Range(), s
                    return (
                        Range(start, length, right.startswith("0")),
                        suffix,
                    )
        return Range(), s


class LocationContext:
    """Per-operation context: conflict policy, shared HTTP session,
    optional profiler, optional location-health scoreboard
    (src/file/location.rs:447-510).

    ``health`` (a ``cluster.health.HealthScoreboard``, duck-typed to
    avoid a file->cluster import cycle) receives a completion record
    for every read / write_subfile / read_view_mapper hit against a
    location — the feed for latency-ranked ordering, the per-location
    breaker, and the hedged-read delay.  ``read_retries`` bounds the
    per-location transient-HTTP retry loops in the read fall-through
    (file/file_part.py) and the shard-write failover
    (cluster/destination.py)."""

    def __init__(self, on_conflict: str = OVERWRITE,
                 profiler: Optional[Profiler] = None,
                 https_only: bool = False,
                 user_agent: Optional[str] = None,
                 read_retries: int = 1):
        if on_conflict not in (OVERWRITE, IGNORE):
            raise ValueError(f"invalid on_conflict {on_conflict!r}")
        self.on_conflict = on_conflict
        self.profiler = profiler
        self.https_only = https_only
        self.user_agent = user_agent
        self.read_retries = max(int(read_retries), 0)
        self.health = None  # set by Cluster.__init__ (one per cluster)
        self._sessions: dict[int, object] = {}

    def but_with(self, *, on_conflict: Optional[str] = None,
                 profiler: Optional[Profiler] = None) -> "LocationContext":
        cx = LocationContext(
            on_conflict=on_conflict or self.on_conflict,
            profiler=profiler if profiler is not None else self.profiler,
            https_only=self.https_only,
            user_agent=self.user_agent,
            read_retries=self.read_retries,
        )
        cx.health = self.health  # one scoreboard per cluster
        cx._sessions = self._sessions  # share the connection pools
        return cx

    def http_session(self):
        """The aiohttp session for the running loop (loop-bound, cached).

        Entries are validated against a weakref of their loop: ``id()``
        of a dead loop can be recycled by a new one, and handing out a
        session bound to a dead loop would fail strangely.  Each new
        session also arms a primed async generator whose finalizer
        closes it — ``asyncio.run``'s ``shutdown_asyncgens`` then tears
        the session down while its loop is still alive, so short-lived
        loops (tests, scripts) don't leak connectors even when nobody
        calls :meth:`aclose`."""
        import weakref

        import aiohttp

        loop = asyncio.get_running_loop()
        entry = self._sessions.get(id(loop))
        if entry is not None:
            loop_ref, sess = entry[0], entry[1]
            if loop_ref() is loop and not sess.closed:
                return sess
            del self._sessions[id(loop)]  # stale: dead/recycled loop
        headers = {}
        if self.user_agent:
            headers["User-Agent"] = self.user_agent
        sess = aiohttp.ClientSession(headers=headers)

        async def _closer():
            try:
                yield
            finally:
                if not sess.closed:
                    await sess.close()

        gen = _closer()
        # entries for dead loops can't be awaited-closed anymore; sweep
        # them here so a long-lived process running many short loops
        # doesn't pin one (ref, session, gen) tuple per dead loop
        for key, (ref, _s, _g, _p) in list(self._sessions.items()):
            if ref() is None:
                del self._sessions[key]
        # Prime it so the loop tracks the generator and finalizes it at
        # shutdown_asyncgens.  The cache entry holds the strong ref
        # (stored on the very next line so no statement can strand the
        # primer): the loop's own asyncgen registry is a WeakSet, and an
        # unreferenced suspended generator would be GC-finalized — and
        # close the session — while the loop is still serving.
        primer = asyncio.ensure_future(gen.__anext__())
        self._sessions[id(loop)] = (weakref.ref(loop), sess, gen, primer)
        return sess

    async def aclose(self) -> None:
        loop = asyncio.get_running_loop()
        entry = self._sessions.pop(id(loop), None)
        if entry is not None:
            _ref, sess, gen, primer = entry
            if not primer.done():
                primer.cancel()
            # retrieve the primer's outcome either way: closing the
            # generator before the primer ran leaves it dying with
            # StopAsyncIteration, which must not surface as a
            # never-retrieved task exception
            try:
                # lint: unbounded-deadline-ok primer is done or was
                # cancelled two lines up — this await only retrieves
                # the already-settled outcome
                await primer
            except (asyncio.CancelledError, StopAsyncIteration):
                pass
            await gen.aclose()  # runs the closer's finally
            if not sess.closed:
                await sess.close()


_DEFAULT_CONTEXT = LocationContext()


def default_context() -> LocationContext:
    return _DEFAULT_CONTEXT


#: Atomic local publication stages "<target>.tmp.<pid>.<8-hex>" and
#: os.replace()s it in; GC's stale-temp reaping (cli/main.py) and its
#: tests key off these same definitions so the format can't drift.
_PUBLISH_TEMP_RE = re.compile(r"\.tmp\.\d+\.[0-9a-f]{8}$")


def publish_temp_name(target: str) -> str:
    """The staging path for an atomic publication of ``target``."""
    return f"{target}.tmp.{os.getpid()}.{os.urandom(4).hex()}"


def is_publish_temp(name: str) -> bool:
    """True when ``name`` (a basename or path) is an atomic-publication
    temp file — invisible to readers until renamed, so one older than
    any reasonable write duration is a crashed writer's leak."""
    return _PUBLISH_TEMP_RE.search(name) is not None


def _publish_sync(target: str, data, ignore_existing: bool) -> str:
    """Publish one whole-buffer local chunk, start to end, in the
    calling thread: the single implementation of the local publish
    protocol, run by ``Location.write`` as ONE worker-thread call (no
    filesystem syscall on the event loop, one loop round trip per
    chunk).  Returns the outcome, counted in ``cb_chunk_publish_total``:
    ``skipped`` (``ignore_existing`` and the target exists), ``direct``
    or ``renamed``.

    Regular-file targets are written to a sibling temp file and
    os.replace()d in, so a concurrent reader (including page-cache views
    from ``read_view``) never observes a torn or in-place-truncated
    file — the reference's direct open-truncate-write
    (src/file/location.rs:219-236) has that window.  Crash durability
    follows the filesystem's rename semantics (flush, no fsync —
    matching the reference's flush-only behavior): after power loss the
    path holds the old content, the new content, or on some filesystems
    an empty file, but never a torn mix.  This is machine-verified, not
    argued: the ops ride the filesystem seam (``file/fsio.py``) and the
    crash harness (sim/crash.py ``chunk_publish``/``repair_rewrite``,
    bench ``--config 16``) replays every crash point of this protocol —
    kill, torn temp write, power-cut writeback orders — asserting the
    published path is only ever old | new | content-address-detectable,
    and that crashed writers' temps stay reapable without touching it.
    (Chunk publication stays flush-only by design; metadata publication,
    the cluster's write acknowledgment, adds the fsync+dir-fsync
    barriers — cluster/metadata.py.)  Direct writes are kept for
    symlinks (write through, preserving the link), special targets
    (devices, fifos — rename would replace the node), and as a fallback
    when the parent directory refuses temp creation (EACCES/EPERM/EROFS
    — the in-place write only needs permission on the file itself).  An
    existing regular file's permission bits carry over to the
    replacement; ownership becomes the writing process's and hard links
    detach — correct for content-addressed chunks, where an in-place
    rewrite would mutate every linked path.

    Nothing here awaits, so a cancellation cannot land between the
    finished temp write and the rename.  The caller's cancellation
    reaches it at once, and the worker cannot be interrupted: a thread
    the executor has started runs this to its end, publishing or
    reaping its own temp, and a call cancelled before its thread starts
    never runs.  A chunk published for a request that was cancelled is
    an orphan for ``find-unused-hashes``, like the shards of any failed
    write."""
    with obs_tracing.span("dest.publish", bytes=len(data)):
        exists, direct, mode = _inspect_target(target)
        if exists and ignore_existing:
            outcome = "skipped"
        elif direct:
            _write_file(target, data)
            outcome = "direct"
        else:
            tmp = publish_temp_name(target)
            try:
                _write_file(tmp, data)
            except OSError as err:
                if _reap_publish_temp(tmp) \
                        or err.errno not in _TEMP_REFUSED:
                    raise
                _write_file(target, data)
                outcome = "direct"
            except BaseException:
                _reap_publish_temp(tmp)
                raise
            else:
                _swap_in(tmp, target, mode)
                outcome = "renamed"
    obs_metrics.record_chunk_publish(outcome)
    return outcome


def _inspect_target(target: str) -> tuple[bool, bool, Optional[int]]:
    """(exists?, write-direct?, preserved mode) of a publication target
    from one ``lstat`` — plus a ``stat`` only for a symlink, whose
    existence is its referent's.  The answers ``os.path.exists`` /
    ``islink`` / ``isfile`` / ``stat`` would give: a symlink or a
    special file is written directly, a regular file's permission bits
    carry over to its replacement."""
    try:
        st = os.lstat(target)
    except OSError:
        return False, False, None
    if stat.S_ISLNK(st.st_mode):
        return os.path.exists(target), True, None
    if not stat.S_ISREG(st.st_mode):
        return True, True, None
    return True, False, st.st_mode & 0o7777


def _write_file(path: str, data) -> None:
    with _fsio.open(path, "wb") as f:
        f.write(data)
        f.flush()


#: errnos of a directory that refuses the temp file, where an in-place
#: write may still be allowed
_TEMP_REFUSED = (errno.EACCES, errno.EPERM, errno.EROFS)


def _swap_in(tmp: str, target: str, mode: Optional[int]) -> None:
    """Rename a finished temp over its target (carrying ``mode`` over);
    on any failure the temp is reaped before the error propagates."""
    try:
        if mode is not None:
            os.chmod(tmp, mode)
        _fsio.replace(tmp, target)
    except BaseException:
        _reap_publish_temp(tmp)
        raise


def _reap_publish_temp(tmp: str) -> bool:
    """Remove a staging temp; True when it existed (i.e. the write got
    far enough to create it — the EACCES-fallback discriminator)."""
    created = os.path.exists(tmp)
    try:
        _fsio.unlink(tmp)
    except OSError:
        pass
    return created


async def _atomic_publish_stream(reader, target: str) -> int:
    """The streaming local write: ``_publish_sync``'s protocol with the
    body copied from ``reader`` on the loop; the target inspection, the
    rename and the reap of a failed temp run in worker calls."""
    _exists, direct, mode = await asyncio.to_thread(_inspect_target,
                                                    target)
    if direct:
        return await aio.copy_reader_to_file(reader, target)
    tmp = publish_temp_name(target)
    try:
        total = await aio.copy_reader_to_file(reader, tmp)
    except OSError as err:
        if await asyncio.to_thread(_reap_publish_temp, tmp) \
                or err.errno not in _TEMP_REFUSED:
            raise
        return await aio.copy_reader_to_file(reader, target)
    except BaseException:
        await asyncio.to_thread(_reap_publish_temp, tmp)
        raise
    await asyncio.to_thread(_swap_in, tmp, target, mode)
    return total


class _HttpBodyReader:
    """Wraps an aiohttp response body as an AsyncByteReader, closing the
    response at EOF (or on close(), for early-stopping consumers)."""

    def __init__(self, resp):
        self._resp = resp

    async def read(self, n: int = -1) -> bytes:
        if self._resp is None:
            return b""
        try:
            if n < 0:
                data = await self._resp.content.read()
            else:
                data = await self._resp.content.read(n)
        except Exception as err:
            # mid-body failures must surface as LocationError so per-location
            # failover (FilePart.read) can fall through to other replicas
            self._resp.close()
            self._resp = None
            raise LocationError(f"http body read failed: {err}") from err
        if not data:
            self._resp.release()
            self._resp = None
        return data

    async def close(self) -> None:
        if self._resp is not None:
            self._resp.release()
            self._resp = None


def _io_done(location: "Location", cx: "LocationContext", op: str,
             ok: bool, err: Optional[str], nbytes: int,
             start: float) -> None:
    """One location read or write completed: the ``cb_io_*`` series
    always, the context's profiler log when one is attached."""
    obs_metrics.record_io(op, ok, nbytes, _clock.monotonic() - start)
    if cx.profiler is not None:
        log = cx.profiler.log_read if op == "read" \
            else cx.profiler.log_write
        log(ok, err, location, nbytes, start)


class _CountedReader:
    """Counts streamed bytes and records exactly one I/O completion when
    the stream ends — EOF, error, or early close (the reference's
    streaming paths are unprofiled, ``// TODO: Profiler``
    src/file/location.rs:119,255)."""

    def __init__(self, base, cx: "LocationContext", location: "Location",
                 start: float):
        self._base = base
        self._cx = cx
        self._location = location
        self._start = start
        self._total = 0
        self._logged = False

    def _log(self, ok: bool, err: Optional[str] = None) -> None:
        if not self._logged:
            self._logged = True
            _io_done(self._location, self._cx, "read", ok, err,
                     self._total, self._start)

    async def read(self, n: int = -1) -> bytes:
        try:
            data = await self._base.read(n)
        except Exception as err:
            self._log(False, str(err))
            raise
        if data:
            self._total += len(data)
        else:
            self._log(True)
        return data

    async def close(self) -> None:
        self._log(True)
        await aio.close_reader(self._base)


@dataclass(frozen=True, order=True)
class Location:
    """A storage address; value semantics, string serde."""

    kind: str  # "local" | "http" | "slab" | "sim"
    target: str  # filesystem path, full URL, slab <root>/<name>, or
    #            sim <fabric>/<node>/<chunk> path
    range: Range = field(default_factory=Range)

    # ---- construction / parsing ----

    @staticmethod
    def parse(s: str) -> "Location":
        rng, rest = Range.from_str_prefix(s)
        if rest.startswith("http://") or rest.startswith("https://"):
            parts = urlsplit(rest)
            if not parts.netloc:
                raise LocationParseError(f"invalid http url: {rest!r}")
            return Location("http", rest, rng)
        if rest.startswith("file://"):
            parts = urlsplit(rest)
            path = parts.path
            if not path.startswith("/"):
                raise LocationParseError("file:// path must be absolute")
            return Location("local", path, rng)
        if rest.startswith("slab:"):
            # packed slab store address (file/slab.py): the path names
            # <store root>/<chunk name> — chunk bytes live inside the
            # root's slab files, addressed through its index
            path = rest[len("slab:"):]
            if not path:
                raise LocationParseError("empty slab location")
            if "://" in path.split("/")[0]:
                raise LocationParseError(
                    f"invalid slab location: {rest!r}")
            return Location("slab", path, rng)
        if rest.startswith("sim:"):
            # simulated storage node (sim/fabric.py): the path names
            # <fabric>/<node>[/<chunk>] — bytes live in the in-process
            # fabric registry, resolved lazily exactly like slab:
            path = rest[len("sim:"):]
            if not path:
                raise LocationParseError("empty sim location")
            if "://" in path.split("/")[0]:
                raise LocationParseError(
                    f"invalid sim location: {rest!r}")
            return Location("sim", path, rng)
        if "://" in rest.split("/")[0]:
            raise LocationParseError(f"invalid location scheme: {rest!r}")
        if not rest:
            raise LocationParseError("empty location")
        return Location("local", rest, rng)

    @staticmethod
    def local(path: str, rng: Optional[Range] = None) -> "Location":
        return Location("local", str(path), rng or Range())

    @staticmethod
    def slab(path: str, rng: Optional[Range] = None) -> "Location":
        return Location("slab", str(path), rng or Range())

    @staticmethod
    def sim(path: str, rng: Optional[Range] = None) -> "Location":
        return Location("sim", str(path), rng or Range())

    @staticmethod
    def http(url: str, rng: Optional[Range] = None) -> "Location":
        if not (url.startswith("http://") or url.startswith("https://")):
            raise LocationParseError(f"not an http url: {url!r}")
        return Location("http", url, rng or Range())

    def __str__(self) -> str:
        prefix = "slab:" if self.is_slab() else \
            "sim:" if self.is_sim() else ""
        if self.range.is_specified():
            return f"{self.range}{prefix}{self.target}"
        return f"{prefix}{self.target}"

    def is_http(self) -> bool:
        return self.kind == "http"

    def is_local(self) -> bool:
        return self.kind == "local"

    def is_slab(self) -> bool:
        return self.kind == "slab"

    def is_sim(self) -> bool:
        return self.kind == "sim"

    def with_range(self, rng: Range) -> "Location":
        return replace(self, range=rng)

    # ---- slab addressing (file/slab.py) ----

    def _slab_parts(self) -> tuple[str, str]:
        """(store root, chunk name) for a slab chunk address."""
        root, name = os.path.split(self.target.rstrip("/"))
        if not root or not name:
            raise LocationError(
                f"slab location {self.target!r} names a store root, "
                "not a chunk")
        return root, name

    def _slab_store(self):
        from chunky_bits_tpu.file import slab

        return slab.get_store(self._slab_parts()[0])

    # ---- sim addressing (sim/fabric.py) ----

    def _sim_node(self) -> tuple[object, str]:
        """(simulated node, chunk name) for a sim chunk address.  The
        import is lazy and only runs for sim-kind locations — production
        paths never load the simulator (the slab: discipline)."""
        # lint: sim-purity-ok sanctioned inversion: lazy import only on
        # the sim: address branch; tests/test_sim.py pins that the
        # production default-import closure never loads sim/
        from chunky_bits_tpu.sim import fabric as sim_fabric

        return sim_fabric.resolve(self.target)

    def slab_extent(self) -> Optional[tuple[str, int, int]]:
        """(slab file path, offset, length) of a live packed chunk, or
        None (not a slab location / no such chunk).  Sync — may read
        the store's index journal; off-loop callers only."""
        if not self.is_slab():
            return None
        try:
            return self._slab_store().extent_path(self._slab_parts()[1])
        except (OSError, LocationError):
            return None

    # ---- hierarchy (src/file/location.rs:407-436) ----

    def child(self, name: str) -> "Location":
        if not self.is_http():
            return Location(self.kind, os.path.join(self.target, name))
        parts = urlsplit(self.target)
        path = parts.path.rstrip("/") + "/" + quote(name, safe="")
        return Location(
            "http", urlunsplit(parts._replace(path=path)))

    def is_child_of(self, other: "Location") -> bool:
        if self.range.is_specified():
            return False
        if self.kind != other.kind:
            return False
        if not self.is_http():
            return os.path.dirname(self.target) == other.target.rstrip("/") \
                or os.path.dirname(self.target) == other.target
        left = urlsplit(self.target)
        right = urlsplit(other.target)
        if (left.scheme, left.netloc) != (right.scheme, right.netloc):
            return False
        parent = left.path.rsplit("/", 1)[0]
        return parent == right.path.rstrip("/") or parent == right.path

    def is_parent_of(self, other: "Location") -> bool:
        return other.is_child_of(self)

    def _check_scheme(self, cx: LocationContext) -> None:
        """Enforce the ``https_only`` tunable: plain-http targets are
        refused on every network verb, matching the reference's client
        built with https-only (src/cluster/tunables.rs:25-32)."""
        if cx.https_only and self.target.startswith("http://"):
            raise LocationError(
                f"https_only is set: refusing plain-http location "
                f"{self.target}"
            )

    def _redirect_kwargs(self, cx: LocationContext) -> dict:
        """Request kwargs for the mutating/HEAD verbs: under https_only,
        redirects are not followed (a replayed PUT body could otherwise
        travel a plain-http hop before any post-hoc check)."""
        return {"allow_redirects": False} if cx.https_only else {}

    def _check_redirect(self, cx: LocationContext, resp) -> None:
        """Refuse 3xx answers under https_only (paired with
        ``_redirect_kwargs``); without the tunable aiohttp has already
        followed them."""
        if cx.https_only and 300 <= resp.status < 400:
            resp.release()
            raise LocationError(
                f"https_only is set: refusing redirect "
                f"({resp.status}) from {self.target}"
            )

    def _check_response_hops(self, cx: LocationContext, resp) -> None:
        """For GET (where the body is not consumed until after this
        check): refuse if any redirect hop or the final URL travelled
        plain http."""
        if not cx.https_only:
            return
        for r in (*resp.history, resp):
            if r.url.scheme == "http":
                resp.release()
                raise LocationError(
                    f"https_only is set: response for {self.target} "
                    f"travelled plain http via {r.url}"
                )

    # ---- read path ----

    async def reader(self, cx: Optional[LocationContext] = None
                     ) -> aio.AsyncByteReader:
        """Open a streaming reader honoring the range
        (src/file/location.rs:115-183).  I/O-hooked: one completion per
        stream at EOF/close/error — the streaming-path hook the reference
        leaves as TODO (src/file/location.rs:119)."""
        cx = cx or default_context()
        start = _clock.monotonic()
        try:
            base = await self._open_reader(cx)
        except LocationError as err:
            # stream-open failure: one health sample (latency to the
            # error), one profiler entry
            if cx.health is not None:
                cx.health.record(self, False, _clock.monotonic() - start)
            _io_done(self, cx, "read", False, str(err), 0, start)
            raise
        if cx.health is not None:
            # the scoreboard times the open (time-to-first-byte proxy);
            # stream duration depends on the consumer, not the node
            cx.health.record(self, True, _clock.monotonic() - start)
        if cx.profiler is None:
            # the bare reader keeps its zero-copy surfaces (readinto,
            # view_parts); chunk reads go through read()/read_view()
            return base
        return _CountedReader(base, cx, self, start)

    async def _open_reader(self, cx: LocationContext
                           ) -> aio.AsyncByteReader:
        rng = self.range
        if self.is_slab():
            # packed chunk: one indexed open+seek into the slab file,
            # bounded by the extent (the slab-plane analogue of the
            # one-file open below; short ranges read short, exactly
            # like a local file that ends early)
            if rng.start < 0 or (rng.length is not None
                                 and rng.length < 0):
                raise LocationError(
                    f"negative range {rng} on slab location")
            root, name = self._slab_parts()
            store = self._slab_store()

            def _open():
                ext = store.lookup(name)
                if ext is None:
                    raise FileNotFoundError(
                        f"no live chunk {name!r} in slab store {root}")
                f = open(store.slab_path(ext.slab), "rb")
                try:
                    f.seek(ext.offset + rng.start)
                except BaseException:
                    f.close()
                    raise
                return f, ext

            try:
                # cancel-safe hop: a scrub restart or hedge loser
                # cancelled mid-open must not orphan the slab handle
                f, ext = await aio.open_in_thread(
                    _open, lambda r: r[0].close())
            except OSError as err:
                raise LocationError(str(err)) from err
            base = aio.FileReader(store.slab_path(ext.slab), fileobj=f)
            avail = max(ext.length - rng.start, 0)
            if rng.length is None:
                return aio.TakeReader(base, avail)
            if rng.extend_zeros:
                return aio.ZeroExtendReader(
                    aio.TakeReader(base, min(avail, rng.length)),
                    rng.length)
            return aio.TakeReader(base, min(rng.length, avail))
        if self.is_sim():
            # simulated node: the fabric applies latency/fault/bandwidth
            # models and returns the (ranged) payload; range semantics
            # mirror the local branch (short ranges read short,
            # extend_zeros pads)
            if rng.start < 0 or (rng.length is not None
                                 and rng.length < 0):
                raise LocationError(
                    f"negative range {rng} on sim location")
            node, name = self._sim_node()
            data = await node.read(name, rng.start, rng.length)
            base = aio.BytesReader(data)
            if rng.length is not None and rng.extend_zeros:
                return aio.ZeroExtendReader(base, rng.length)
            return base
        if self.is_local():
            def _open_local():
                f = open(self.target, "rb")
                try:
                    if rng.start:
                        f.seek(rng.start)
                except BaseException:
                    f.close()
                    raise
                return f

            try:
                f = await aio.open_in_thread(
                    _open_local, lambda h: h.close())
            except OSError as err:
                raise LocationError(str(err)) from err
            base = aio.FileReader(self.target, fileobj=f)
            if rng.length is None:
                return base
            if rng.extend_zeros:
                return aio.ZeroExtendReader(base, rng.length)
            return aio.TakeReader(base, rng.length)
        # HTTP
        self._check_scheme(cx)
        headers = {}
        if rng.is_specified():
            if rng.length is not None:
                headers["Range"] = \
                    f"bytes={rng.start}-{rng.start + rng.length - 1}"
            else:
                headers["Range"] = f"bytes={rng.start}-"
        sess = cx.http_session()
        try:
            resp = await sess.get(self.target, headers=headers,
                                  **self._redirect_kwargs(cx))
        except Exception as err:
            raise LocationError(f"http get failed: {err}") from err
        # Under https_only the request ran with redirects disabled, so a
        # 3xx is refused before any follow-up leaves the machine; the hop
        # check is belt-and-braces.
        self._check_redirect(cx, resp)
        self._check_response_hops(cx, resp)
        if resp.status >= 400:
            resp.release()
            raise HttpStatusError(resp.status, self.target)
        if rng.is_specified() and resp.status != 206:
            resp.release()
            raise HttpStatusError(resp.status, self.target)
        if not rng.is_specified() and resp.status != 200:
            resp.release()
            raise HttpStatusError(resp.status, self.target)
        base = _HttpBodyReader(resp)
        if rng.length is None:
            return base
        if rng.extend_zeros:
            return aio.ZeroExtendReader(base, rng.length)
        return aio.TakeReader(base, rng.length)

    async def read(self, cx: Optional[LocationContext] = None) -> bytes:
        """Read the full (ranged) content; I/O-hooked
        (src/file/location.rs:95-113)."""
        cx = cx or default_context()
        start = _clock.monotonic()
        if cx.health is not None:
            cx.health.begin(self)
        try:
            # _open_reader, not reader(): this whole-buffer op records its
            # own single I/O completion below.
            reader = await self._open_reader(cx)
            try:
                chunks = []
                while True:
                    data = await reader.read(1 << 20)
                    if not data:
                        break
                    chunks.append(data)
                out = b"".join(chunks)
            finally:
                # EOF does not release the underlying file/response;
                # an unclosed handle per whole-buffer read leaks fds
                # (surfaces as ResourceWarning under -W error)
                await aio.close_reader(reader)
        except LocationError as err:
            if cx.health is not None:
                cx.health.finish(self, False, _clock.monotonic() - start)
            _io_done(self, cx, "read", False, str(err), 0, start)
            raise
        except BaseException:
            # cancellation (a hedge loser) or a non-Location failure:
            # close out the in-flight count without a latency/error
            # sample — a cancelled racer says nothing about the node
            if cx.health is not None:
                cx.health.finish(self, None, None)
            raise
        if cx.health is not None:
            cx.health.finish(self, True, _clock.monotonic() - start)
        _io_done(self, cx, "read", True, None, len(out), start)
        return out

    async def read_view(self, cx: Optional[LocationContext] = None
                        ) -> Optional[memoryview]:
        """Zero-copy page-cache view of a local (optionally ranged)
        file, or ``None`` when the fast path doesn't apply — non-local
        targets, an active profiler (which must see the generic read),
        ``CHUNKY_BITS_TPU_NO_MMAP=1``, ranges reaching past EOF (the
        generic path owns short-read/extend-zeros semantics), or
        unmappable files.

        The read-only view keeps its backing map alive for its own
        lifetime.  Chunk files are published atomically (``write`` and
        streaming local writes replace via rename, never truncating a
        regular file in place), so a concurrent re-write of the same
        location can never invalidate a view already taken — the old
        inode stays mapped.  A file truncated by an *external* writer
        can still SIGBUS a held view; set ``CHUNKY_BITS_TPU_NO_MMAP=1``
        for clusters whose storage is shared with such writers."""
        mapper = self.read_view_mapper(cx)
        if mapper is None:
            return None
        return await asyncio.to_thread(mapper)

    def read_view_mapper(self, cx: Optional[LocationContext] = None):
        """The synchronous mapper behind :meth:`read_view` (or ``None``
        when the zero-copy path doesn't apply).  Callers already inside
        a worker thread can run it there and fuse their own sync work
        (e.g. hash verification) into the same thread hop — per-chunk
        hop latency, not bytes, dominates warm local reads on small
        hosts."""
        cx = cx or default_context()
        if (not (self.is_local() or self.is_slab())
                or cx.profiler is not None
                or aio.mmap_opted_out()):
            return None
        rng = self.range
        health = cx.health  # thread-safe scoreboard; _map runs off-loop
        if self.is_slab():
            try:
                root_name = self._slab_parts()
            except LocationError:
                return None
            store = self._slab_store()
            location = self

            def _map_slab() -> Optional[memoryview]:
                t0 = _clock.monotonic()
                view = store.map_view(root_name[1], rng.start or 0,
                                      rng.length)
                if view is not None:
                    if health is not None:
                        health.record(location, True,
                                      _clock.monotonic() - t0)
                    obs_metrics.record_io("read", True, len(view),
                                          _clock.monotonic() - t0)
                return view

            return _map_slab

        def _map() -> Optional[memoryview]:
            import mmap

            t0 = _clock.monotonic()
            try:
                with open(self.target, "rb") as f:
                    mm = mmap.mmap(f.fileno(), 0,
                                   access=mmap.ACCESS_READ)
            except (OSError, ValueError, io.UnsupportedOperation):
                return None
            start = rng.start or 0
            if start < 0 or (rng.length is not None and rng.length < 0):
                # negative ranges: the generic path owns the error
                # (Python slicing would silently serve bytes from EOF)
                mm.close()
                return None
            end = len(mm) if rng.length is None else start + rng.length
            if end > len(mm) or start > len(mm):
                # short range / zero-extension: generic path semantics
                mm.close()
                return None
            if health is not None:
                # a None return above is "fast path doesn't apply", not
                # a node failure — the generic read re-records it; only
                # a served view is a health sample
                health.record(self, True, _clock.monotonic() - t0)
            obs_metrics.record_io("read", True, end - start,
                                  _clock.monotonic() - t0)
            return memoryview(mm)[start:end]

        return _map

    # ---- write path ----

    async def write(self, data: bytes,
                    cx: Optional[LocationContext] = None) -> None:
        """Whole-buffer write with conflict policy; I/O-hooked and
        spanned as ``dest.write`` (src/file/location.rs:185-244).  A
        local target is published by one worker call that also makes
        the conflict check (``_publish_sync``)."""
        cx = cx or default_context()
        if self.range.is_specified():
            raise WriteToRangeError()
        with obs_tracing.span("dest.write", bytes=len(data)):
            start = _clock.monotonic()
            if cx.health is not None:
                cx.health.begin(self)
            try:
                if cx.on_conflict == IGNORE and not self.is_local() \
                        and await self.file_exists(cx):
                    if cx.health is not None:
                        cx.health.finish(self, True,
                                         _clock.monotonic() - start)
                    _io_done(self, cx, "write", True, None, len(data), start)
                    return
                if self.is_slab():
                    # packed publication: slab append + journal commit
                    # (file/slab.py's atomic-index protocol) — the slab
                    # plane's equivalent of the rename publication below
                    root, name = self._slab_parts()
                    store = self._slab_store()
                    try:
                        await asyncio.to_thread(store.append, name, data)
                    except OSError as err:
                        raise LocationError(str(err)) from err
                elif self.is_sim():
                    node, name = self._sim_node()
                    await node.write(name, data)
                elif self.is_local():
                    try:
                        await asyncio.to_thread(
                            _publish_sync, self.target, data,
                            cx.on_conflict == IGNORE)
                    except OSError as err:
                        raise LocationError(str(err)) from err
                else:
                    self._check_scheme(cx)
                    sess = cx.http_session()
                    try:
                        resp = await sess.put(self.target, data=data,
                                              **self._redirect_kwargs(cx))
                        resp.release()
                    except Exception as err:
                        raise LocationError(f"http put failed: {err}") from err
                    self._check_redirect(cx, resp)
                    if resp.status >= 400:
                        raise HttpStatusError(resp.status, self.target)
            except LocationError as err:
                if cx.health is not None:
                    cx.health.finish(self, False, _clock.monotonic() - start)
                _io_done(self, cx, "write", False, str(err), len(data), start)
                raise
            except BaseException:
                if cx.health is not None:
                    cx.health.finish(self, None, None)  # cancelled: no verdict
                raise
            if cx.health is not None:
                cx.health.finish(self, True, _clock.monotonic() - start)
            _io_done(self, cx, "write", True, None, len(data), start)

    async def write_from_reader(self, reader: aio.AsyncByteReader,
                                cx: Optional[LocationContext] = None) -> int:
        """Streaming write; 1 MiB chunks into a chunked HTTP PUT or a local
        file (src/file/location.rs:246-309).  Returns bytes written.
        I/O-hooked (the reference's TODO at location.rs:255)."""
        cx = cx or default_context()
        start = _clock.monotonic()
        # Count consumed bytes on the reader side so a stream that fails
        # mid-body still records its partial progress.
        counted = aio.CountingReader(reader)
        if cx.health is not None:
            cx.health.begin(self)
        try:
            total = await self._write_from_reader_impl(counted, cx)
        except LocationError as err:
            if cx.health is not None:
                cx.health.finish(self, False, _clock.monotonic() - start)
            _io_done(self, cx, "write", False, str(err), counted.total,
                     start)
            raise
        except BaseException:
            if cx.health is not None:
                cx.health.finish(self, None, None)  # cancelled: no verdict
            raise
        if cx.health is not None:
            cx.health.finish(self, True, _clock.monotonic() - start)
        _io_done(self, cx, "write", True, None, total, start)
        return total

    async def _write_from_reader_impl(self, reader: aio.AsyncByteReader,
                                      cx: LocationContext) -> int:
        if self.range.is_specified():
            raise WriteToRangeError()
        if cx.on_conflict == IGNORE and await self.file_exists(cx):
            return 0
        async def drain() -> bytes:
            # whole-body buffering for the one-record publication
            # shapes below (chunk payloads are bounded by the
            # profile's chunksize)
            chunks: list[bytes] = []
            while True:
                data = await reader.read(1 << 20)
                if not data:
                    break
                chunks.append(data)
            return b"".join(chunks)

        if self.is_slab():
            # the slab journal commits (name -> extent) in one record,
            # so the whole body must be known before publication
            payload = await drain()
            root, name = self._slab_parts()
            store = self._slab_store()
            try:
                await asyncio.to_thread(store.append, name, payload)
            except OSError as err:
                raise LocationError(str(err)) from err
            return len(payload)
        if self.is_sim():
            # one fabric publication per chunk (mirrors the slab shape)
            payload = await drain()
            node, name = self._sim_node()
            await node.write(name, payload)
            return len(payload)
        if self.is_local():
            try:
                return await _atomic_publish_stream(reader, self.target)
            except OSError as err:
                raise LocationError(str(err)) from err
        self._check_scheme(cx)
        total = 0

        async def gen():
            nonlocal total
            while True:
                data = await reader.read(1 << 20)
                if not data:
                    break
                total += len(data)
                yield data

        sess = cx.http_session()
        try:
            resp = await sess.put(self.target, data=gen(),
                                  **self._redirect_kwargs(cx))
            resp.release()
        except Exception as err:
            raise LocationError(f"http streaming put failed: {err}") from err
        self._check_redirect(cx, resp)
        if resp.status >= 400:
            raise HttpStatusError(resp.status, self.target)
        return total

    async def write_subfile(self, name: str, data: bytes,
                            cx: Optional[LocationContext] = None
                            ) -> "Location":
        """Write a named child (content-addressed chunk) under this
        location; returns the child (src/file/location.rs:311-343)."""
        target = self.child(name)
        try:
            await target.write(data, cx)
        except LocationError as err:
            raise ShardError(str(err), location=target) from err
        return target

    # ---- management ----

    async def delete(self, cx: Optional[LocationContext] = None) -> None:
        cx = cx or default_context()
        if self.is_slab():
            # GC of a packed chunk marks the extent dead in the index
            # (reclaimed by SlabStore.compact), never punches the slab
            root, name = self._slab_parts()
            store = self._slab_store()
            try:
                await asyncio.to_thread(store.mark_dead, name)
            except OSError as err:
                raise LocationError(str(err)) from err
        elif self.is_sim():
            node, name = self._sim_node()
            await node.delete(name)
        elif self.is_local():
            try:
                await asyncio.to_thread(_fsio.unlink, self.target)
            except OSError as err:
                raise LocationError(str(err)) from err
        else:
            self._check_scheme(cx)
            sess = cx.http_session()
            try:
                resp = await sess.delete(self.target,
                                         **self._redirect_kwargs(cx))
                resp.release()
            except Exception as err:
                raise LocationError(f"http delete failed: {err}") from err
            self._check_redirect(cx, resp)
            if resp.status >= 400:
                raise HttpStatusError(resp.status, self.target)

    async def file_exists(self, cx: Optional[LocationContext] = None) -> bool:
        cx = cx or default_context()
        if self.is_slab():
            store = self._slab_store()
            name = self._slab_parts()[1]
            return await asyncio.to_thread(store.lookup, name) is not None
        if self.is_sim():
            node, name = self._sim_node()
            return await node.exists(name)
        if self.is_local():
            return await asyncio.to_thread(os.path.exists, self.target)
        self._check_scheme(cx)
        sess = cx.http_session()
        try:
            resp = await sess.head(self.target,
                                   **self._redirect_kwargs(cx))
            resp.release()
        except Exception as err:
            raise LocationError(f"http head failed: {err}") from err
        self._check_redirect(cx, resp)
        return resp.status < 400

    async def file_len(self, cx: Optional[LocationContext] = None) -> int:
        cx = cx or default_context()
        if self.is_slab():
            store = self._slab_store()
            name = self._slab_parts()[1]
            ext = await asyncio.to_thread(store.lookup, name)
            if ext is None:
                raise LocationError(
                    f"no live chunk {name!r} in slab store")
            return ext.length
        if self.is_sim():
            node, name = self._sim_node()
            return await node.length(name)
        if self.is_local():
            try:
                st = await asyncio.to_thread(os.stat, self.target)
            except OSError as err:
                raise LocationError(str(err)) from err
            return st.st_size
        self._check_scheme(cx)
        sess = cx.http_session()
        try:
            resp = await sess.head(self.target,
                                   **self._redirect_kwargs(cx))
            resp.release()
        except Exception as err:
            raise LocationError(f"http head failed: {err}") from err
        self._check_redirect(cx, resp)
        if resp.status >= 400:
            raise HttpStatusError(resp.status, self.target)
        length = resp.headers.get("Content-Length")
        if length is None:
            raise LocationError(f"no Content-Length from {self.target}")
        return int(length)

    # ---- shard writing (ShardWriter for Location,
    #      src/file/location.rs:605-616) ----

    async def write_shard(self, hash_: AnyHash, data: bytes,
                          cx: Optional[LocationContext] = None
                          ) -> list["Location"]:
        loc = await self.write_subfile(str(hash_), data, cx)
        return [loc]
