"""Decide ``correct``: what the window produced against the plain
reference (``reference.py``), after the window has closed.

PUT: a sample of the acknowledged PUTs drawn from the seed (the mix's
``check_objects``, the largest always among them) is rebuilt from the
seed and encoded by the reference.  For each, the stored file reference
(read as JSON from the metadata directory) must give the object's length
and part layout, every chunk's recorded SHA-256 must equal the
reference's, and every location must hold a file named by that address
whose bytes hash to it.

GET: the load generator hashed every body it received; each must equal
the SHA-256 of the object as the seed makes it.

Each compared number has its limit; mismatches are exact, so their
limit is 0.  A request that failed is not correct either.
"""

from __future__ import annotations

import hashlib
import json
import os
import random

import reference
import store
from payload import Payload


def _sha_file(path: str) -> str | None:
    h = hashlib.sha256()
    try:
        with open(path, "rb") as f:
            while block := f.read(1 << 22):
                h.update(block)
    except OSError:
        return None
    return h.hexdigest()


def _check_put(work: str, config: dict, payload: Payload, name: str,
               size: int) -> tuple[int, int]:
    """``(file-reference mismatches, chunk mismatches)`` of one object."""
    prof = config["profile"]
    d, p = prof["data"], prof["parity"]
    want = reference.expected_chunks(
        payload.body(int(name.rsplit("/", 1)[1]), size), d, p,
        1 << prof["chunk_size"])
    try:
        with open(store.meta_path(work, name)) as f:
            ref = json.load(f)
    except (OSError, ValueError):
        return 1, sum(len(h) for _, h in want)
    bad_ref = int(ref.get("length") != size
                  or len(ref.get("parts", [])) != len(want))
    bad_chunks = 0
    for i, (chunksize, hashes) in enumerate(want):
        part = ref["parts"][i] if i < len(ref.get("parts", [])) else {}
        chunks = part.get("data", []) + part.get("parity", [])
        if (part.get("chunksize") != chunksize
                or len(part.get("data", [])) != d
                or len(part.get("parity", [])) != p):
            bad_ref += 1
        for j, digest in enumerate(hashes):
            c = chunks[j] if j < len(chunks) else {}
            locs = c.get("locations", [])
            ok = c.get("sha256") == digest and bool(locs) and all(
                os.path.basename(loc) == f"sha256-{digest}"
                and _sha_file(loc) == digest for loc in locs)
            bad_chunks += not ok
    return bad_ref, bad_chunks


def compare(work: str, cell, seed: int, records: list, names: list,
            failed: int, counters: dict) -> dict:
    traffic, config = cell.traffic, cell.config
    checks = {"failed_requests": {"value": failed, "limit": 0,
                                  "ok": failed == 0}}
    puts = [r for r in records if r[0] == "put" and r[3] == 200]
    gets = [r for r in records if r[0] == "get" and r[3] == 200]
    ops = store.ops_of(traffic)
    if "put" in ops:
        rng = random.Random(f"{seed}:check")
        want = int(traffic.get("check_objects", 4))
        largest = max(puts, key=lambda r: r[4], default=None)
        sample = rng.sample(puts, min(want, len(puts)))
        if largest is not None and largest not in sample:
            sample[-1:] = [largest]
        payload = Payload(seed, store.max_size(traffic, "object_bytes"))
        bad_ref = bad_chunks = 0
        for r in sample:
            a, b = _check_put(work, config, payload, r[5], r[4])
            bad_ref += a
            bad_chunks += b
        checks["puts_checked"] = {"value": len(sample), "limit": 1,
                                  "ok": len(sample) >= 1}
        checks["file_ref_mismatches"] = {"value": bad_ref, "limit": 0,
                                         "ok": bad_ref == 0}
        checks["chunk_mismatches"] = {"value": bad_chunks, "limit": 0,
                                      "ok": bad_chunks == 0}
    if "get" in ops:
        size = int(traffic["store_object_bytes"])
        payload = Payload(seed, size)
        digests = {}
        bad = 0
        for r in gets:
            if r[5] not in digests:
                digests[r[5]] = hashlib.sha256(payload.body(
                    store.store_index(r[5]), size)).hexdigest()
            bad += r[6] != digests[r[5]] or r[4] != size
        checks["gets_checked"] = {"value": len(gets), "limit": 1,
                                  "ok": len(gets) >= 1}
        checks["body_mismatches"] = {"value": bad, "limit": 0,
                                     "ok": bad == 0}
        if traffic.get("lost_slots"):
            n = counters["reconstruct_dispatches"]
            checks["decode_dispatches"] = {"value": n, "limit": 1,
                                           "ok": n >= 1}
    return checks
