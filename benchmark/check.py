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

Repair (``resilver`` or ``scrub``): every job succeeded; every chunk of
every part of every object has a location whose file hashes to the
chunk's recorded SHA-256, so each object's last repair made its
redundancy whole again; for a seeded sample of the mix's
``check_objects`` objects the recorded layout and addresses equal the
reference's, with every location holding those bytes; and the repair
went through the path the mix names: a decode dispatch or more for
``resilver``, a repair plan or more run in place for ``scrub``.

The reference is ``reference.py`` unless the configuration names
another module of the benchmark's under ``"reference"``, with the same
``expected_chunks(body, d, p, chunk)``: a deployment on another code
brings its own.

Each compared number has its limit; mismatches are exact, so their
limit is 0.  A request that failed is not correct either.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import importlib.util
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


def _reference(work: str, config: dict):
    """The configuration's reference module: ``reference.py``, or the
    one it names, taken from the checkout's ``benchmark/`` directory
    (``work`` is ``<checkout>/.bench_work``) or else from this one."""
    name = config.get("reference", "reference")
    if name == "reference":
        return reference
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(os.path.dirname(work), "benchmark", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(here, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def _check_put(work: str, ref_mod, config: dict, body, name: str
               ) -> tuple[int, int]:
    """``(file-reference mismatches, chunk mismatches)`` of the object
    ``name`` stored from ``body``."""
    prof = config["profile"]
    d, p = prof["data"], prof["parity"]
    size = len(body)
    want = ref_mod.expected_chunks(body, d, p, 1 << prof["chunk_size"])
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
        ref_mod = _reference(work, config)
        bad_ref = bad_chunks = 0
        for r in sample:
            body = payload.body(int(r[5].rsplit("/", 1)[1]), r[4])
            a, b = _check_put(work, ref_mod, config, body, r[5])
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
    if ops & set(store.REPAIR_OPS):
        checks.update(_check_repair(work, cell, seed, records, names,
                                    counters))
    return checks


def _check_repair(work: str, cell, seed: int, records: list, names: list,
                  counters: dict) -> dict:
    traffic = cell.traffic
    jobs = [r for r in records if r[0] in store.REPAIR_OPS]
    bad_jobs = sum(r[3] != 200 for r in jobs)
    # redundancy: each distinct chunk file is hashed once, in threads
    # (hashlib releases the interpreter lock on large buffers)
    chunks, no_ref = [], 0
    for name in names:
        try:
            with open(store.meta_path(work, name)) as f:
                ref = json.load(f)
        except (OSError, ValueError):
            no_ref += 1
            continue
        chunks += [c for part in ref["parts"]
                   for c in part["data"] + part["parity"]]
    paths = sorted({loc for c in chunks for loc in c["locations"]})
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        digests = dict(zip(paths, pool.map(_sha_file, paths)))
    no_copy = sum(not any(digests[loc] == c["sha256"]
                          for loc in c["locations"]) for c in chunks)
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(names, min(int(traffic.get("check_objects", 4)),
                                   len(names)))
    size = int(traffic["store_object_bytes"])
    payload = Payload(seed, size)
    ref_mod = _reference(work, cell.config)
    bad_ref = bad_chunks = 0
    for name in sample:
        body = payload.body(store.store_index(name), size)
        a, b = _check_put(work, ref_mod, cell.config, body, name)
        bad_ref += a
        bad_chunks += b
    if traffic["op"] == "scrub":
        # plans run in place, of every code; a fallback is not one
        via = "repair_plans"
        n = sum(v for k, v in counters.items() if k.startswith("repair.")
                and k.rsplit(".", 1)[1] in ("plans_copy", "plans_decode",
                                            "plans_msr"))
    else:
        via = "decode_dispatches"
        n = counters["reconstruct_batch_dispatches"]
    return {
        "repairs": {"value": len(jobs), "limit": 1, "ok": len(jobs) >= 1},
        "failed_repairs": {"value": bad_jobs, "limit": 0,
                           "ok": bad_jobs == 0},
        "unreadable_file_refs": {"value": no_ref, "limit": 0,
                                 "ok": no_ref == 0},
        "chunks_without_a_valid_copy": {"value": no_copy, "limit": 0,
                                        "ok": no_copy == 0},
        "objects_checked": {"value": len(sample), "limit": 1,
                            "ok": len(sample) >= 1},
        "file_ref_mismatches": {"value": bad_ref, "limit": 0,
                                "ok": bad_ref == 0},
        "chunk_mismatches": {"value": bad_chunks, "limit": 0,
                             "ok": bad_chunks == 0},
        via: {"value": n, "limit": 1, "ok": n >= 1},
    }
