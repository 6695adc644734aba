"""The cell's store: its cluster description, the objects a GET or a
repair mix reads, the loss a repair mix replays, and the warm-up of
every program the mix dispatches under the profile's erasure code.

Destinations are directories under ``<checkout>/.bench_work/store`` on
the machine's disk, one per shard slot, with a JSON metadata directory
beside them; ``run.py`` removes the whole tree when the run ends.
"""

from __future__ import annotations

import asyncio
import json
import os

from payload import Payload


def cluster_obj(work: str, config: dict, backend: str) -> dict:
    """The cluster of ``config`` with its codec on ``backend``: the
    configuration's optional ``tunables`` object, with ``backend`` set
    by the caller (the configuration's own, or a mix's
    ``store_backend`` for set-up)."""
    root = os.path.join(work, "store")
    disks = [os.path.join(root, f"disk{i:02d}")
             for i in range(config["destinations"])]
    meta = os.path.join(root, "meta")
    for d in disks + [meta]:
        os.makedirs(d, exist_ok=True)
    return {"destinations": [{"location": d} for d in disks],
            "metadata": {"type": "path", "format": "json", "path": meta},
            "tunables": dict(config.get("tunables", {}), backend=backend),
            "profiles": {"default": dict(config["profile"])}}


def meta_path(work: str, name: str) -> str:
    return os.path.join(work, "store", "meta", name)


#: payload indices of stored objects start here, apart from PUT bodies
STORE_BASE = 1 << 30


def store_name(index: int) -> str:
    return f"s/{index}"


def store_index(name: str) -> int:
    return STORE_BASE + int(name.rsplit("/", 1)[1])


def max_size(traffic: dict, key: str) -> int:
    return max(_sizes(traffic, key))


async def build(work: str, config: dict, traffic: dict, seed: int
                ) -> list[str]:
    """Write the mix's ``store_objects`` objects with the mix's
    ``store_backend``, then delete the chunk files of its ``lost_slots``
    in every part.  Returns the objects' names."""
    from chunky_bits_tpu.cluster import Cluster
    from chunky_bits_tpu.utils.aio import BytesReader

    size = int(traffic["store_object_bytes"])
    count = int(traffic["store_objects"])
    payload = Payload(seed, size)
    cluster = Cluster.from_obj(cluster_obj(
        work, config, traffic.get("store_backend", config["backend"])))
    profile = cluster.get_profile(None)
    gate = asyncio.Semaphore(16)

    async def put(i: int) -> None:
        async with gate:
            body = payload.body(STORE_BASE + i, size).tobytes()
            await cluster.write_file(store_name(i), BytesReader(body),
                                     profile)

    await asyncio.gather(*(put(i) for i in range(count)))
    lost = traffic.get("lost_slots", [])
    for i in range(count):
        with open(meta_path(work, store_name(i))) as f:
            ref = json.load(f)
        for part in ref["parts"]:
            chunks = part["data"] + part["parity"]
            for slot in lost:
                for loc in chunks[slot]["locations"]:
                    os.remove(loc)
    return [store_name(i) for i in range(count)]


class Loss:
    """One destination lost, replayed on an object before each repair of
    it: its file reference is put back as set-up wrote it, and every
    chunk file that reference records under the lost directory is
    unlinked.  Each part then misses the one slot that placement gave
    that destination, so the parts of one object miss different slots.
    """

    def __init__(self, work: str, names: list[str],
                 destination: str) -> None:
        self.work = work
        lost = os.path.join(work, "store", destination) + os.sep
        #: name -> the file reference's bytes as set-up wrote them
        self.refs: dict[str, bytes] = {}
        #: name -> the chunk files it records under the lost directory
        self.files: dict[str, list[str]] = {}
        #: name -> the bytes of those chunk files
        self.nbytes: dict[str, int] = {}
        for name in names:
            with open(meta_path(work, name), "rb") as f:
                raw = f.read()
            ref = json.loads(raw)
            self.refs[name] = raw
            sizes = {loc: part["chunksize"] for part in ref["parts"]
                     for chunk in part["data"] + part["parity"]
                     for loc in chunk["locations"] if loc.startswith(lost)}
            self.files[name] = sorted(sizes)
            self.nbytes[name] = sum(sizes.values())

    def apply(self, name: str) -> int:
        """Lose ``name``'s chunks on the destination; returns how many
        chunks it lost."""
        with open(meta_path(self.work, name), "wb") as f:
            f.write(self.refs[name])
        for path in self.files[name]:
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
        return len(self.files[name])


def repair_outcome(report, lost: int) -> tuple[int, int]:
    """``(status, rebuilt chunk bytes)`` of a resilver report: 200 only
    when no part reports an error and all ``lost`` chunks were
    written."""
    parts = report.part_reports
    written = sum(len(r.successful_writes()) for r in parts)
    nbytes = sum(r.file_part.chunksize * len(r.successful_writes())
                 for r in parts)
    ok = written == lost and not report.failed_writes()
    return (200 if ok else 500), nbytes


def scrub_outcome(stats, lost: int, nbytes: int) -> tuple[int, int]:
    """``(status, rebuilt chunk bytes)`` of a scrub pass over one object
    that lost ``lost`` chunks of ``nbytes`` bytes: 200 only when the pass
    rewrote every one of them and no repair failed."""
    ok = stats.repaired == lost and stats.repair_failures == 0
    return (200, nbytes) if ok else (500, 0)


class OneFile:
    """The store's metadata seen as a namespace that holds one file: a
    scrub pass over a cluster with this metadata scrubs that file alone.
    Reads and writes go to the store itself."""

    def __init__(self, metadata, name: str) -> None:
        self.metadata = metadata
        self.name = name

    async def list(self, path: str) -> list:
        from chunky_bits_tpu.cluster.metadata import FileOrDirectory

        # the listing's own top entry, then its one child
        return [FileOrDirectory("directory", path),
                FileOrDirectory("file", self.name)]

    async def read(self, path: str):
        return await self.metadata.read(path)

    async def write(self, path: str, payload) -> None:
        await self.metadata.write(path, payload)


def bytes_on_disk(work: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(os.path.join(work, "store")):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


def shard_lengths(config: dict, sizes, shard_len) -> dict[int, int]:
    """Shard length -> the most parts of that length one object of these
    sizes has; ``shard_len`` is the coder's, which rounds a part's
    length up to its shard length."""
    d = config["profile"]["data"]
    chunk = 1 << config["profile"]["chunk_size"]
    out: dict[int, int] = {}
    for size in sizes:
        if size >= d * chunk:
            s = shard_len(d * chunk)
            out[s] = max(out.get(s, 0), size // (d * chunk))
        tail = size % (d * chunk)
        if tail:
            s = shard_len(tail)
            out[s] = max(out.get(s, 0), 1)
    return out


def _sizes(traffic: dict, key: str) -> list[int]:
    v = traffic[key]
    return [s for s, _ in v] if isinstance(v, list) else [v]


#: the repair mixes' ops
REPAIR_OPS = ("resilver", "scrub")


def ops_of(traffic: dict) -> set[str]:
    v = traffic["op"]
    return {o for o, _ in v} if isinstance(v, list) else {v}


def warm(cluster, config: dict, traffic: dict) -> int:
    """Run every codec program the mix can dispatch, by the profile's
    ``code`` (default ``rs``), once at each batch size from 1 up:

    * PUT: encode, up to the mix's ``warm_batches``, or to the parts of
      that length all clients can have in flight if that is fewer;
    * GET: the decode of the lost slots, up to the same;
    * ``resilver``: the decode of one lost slot, up to the parts of that
      length one object's repair has in flight;
    * ``scrub``: the repair planner's plan programs at batch 1: for
      ``rs`` the decode of one whole chunk, read off ``d`` helpers; for
      ``pm-msr`` a helper's projection and the combine of ``d'`` of
      them.

    Each at every shard length the mix's objects have.  Returns the
    calls made."""
    import numpy as np

    from chunky_bits_tpu.ops.backend import get_coder

    prof = config["profile"]
    d, p = prof["data"], prof["parity"]
    code = prof.get("code", "rs")
    coder = get_coder(d, p, config["backend"], code)
    top = int(traffic.get("warm_batches", 1))
    clients = int(traffic["clients"])
    # (input rows, shard length, largest batch, call of [batch, rows, S])
    calls = []
    ops = ops_of(traffic)
    store_sizes = (_sizes(traffic, "store_object_bytes")
                   if "store_object_bytes" in traffic else [])
    store_lengths = shard_lengths(config, store_sizes, coder.shard_len)
    if "put" in ops:
        for s, n in shard_lengths(config, _sizes(traffic, "object_bytes"),
                                  coder.shard_len).items():
            calls.append((d, s, min(top, n * clients), coder.encode_batch))
    lost = sorted(traffic.get("lost_slots", []))
    if "get" in ops and lost:
        present = [i for i in range(d + p) if i not in lost]
        wanted = [i for i in lost if i < d]
        for s, n in store_lengths.items():
            calls.append((d, s, min(top, n * clients),
                          lambda x: coder.reconstruct_batch_picked(
                              x, present, wanted)))
    if "resilver" in ops:
        from chunky_bits_tpu.file.file_reference import RESILVER_CONCURRENCY

        # one destination lost: every part misses one slot, data or
        # parity (the shape is the same whichever).  Each file's repair
        # has its own decode batcher, so a batch holds at most the parts
        # of one object that are in flight at once.
        for s, n in store_lengths.items():
            calls.append((d, s, min(n, RESILVER_CONCURRENCY),
                          lambda x: coder.reconstruct_batch_picked(
                              x, list(range(1, d + p)), [0])))
    if "scrub" in ops:
        # a pass repairs an object's parts one after another, each with
        # a decode batcher of its own: every plan runs at batch 1
        for s in store_lengths:
            if code == "pm-msr":
                helpers = list(range(1, coder.helpers + 1))
                calls.append((1, s, 1,
                              lambda x: coder.project_batch(0, x[:, 0])))
                calls.append((coder.helpers, s // coder.alpha, 1,
                              lambda x: coder.repair_batch(0, helpers, x)))
            else:
                # d helpers in, the other p rows (the lost one among
                # them) out
                calls.append((d, s, 1,
                              lambda x: coder.reconstruct_batch_picked(
                                  x, list(range(p, d + p)),
                                  list(range(p)))))
    # the single-chip backend takes an rs coder's input from an
    # uncommitted jax.device_put, and jit caches committed and
    # uncommitted inputs apart: warm with uncommitted zeros made on the
    # device (no copy in).  A pm-msr coder reshapes its input on the
    # host first, as its callers' arrays are, so it is warmed from the
    # host.
    on_device = config["backend"] == "jax" and code == "rs"
    if on_device:
        import jax.numpy as jnp
    n = 0
    for rows, s, most, call in calls:
        host = None if on_device else np.zeros((most, rows, s), np.uint8)
        for b in range(1, most + 1):
            call(jnp.zeros((b, rows, s), jnp.uint8) if on_device
                 else host[:b])
            n += 1
    return n
