"""The repair cell's pieces on the CPU: the loss it replays, the rate it
reports, and a configuration that brings its own reference and
tunables."""

import asyncio
import json
import os
import sys

import pytest

from benchutil import BENCH, tiny_root
from test_perfbench_cells import SEED

sys.path.insert(0, BENCH)

import run  # noqa: E402
import store  # noqa: E402

GIB = 1 << 30


def test_loss_leaves_one_chunk_missing_per_part_in_varying_slots(tmp_path):
    work = str(tmp_path)
    config = {"profile": {"data": 10, "parity": 4, "chunk_size": 12},
              "destinations": 14, "backend": "native"}
    traffic = {"store_objects": 3,
               "store_object_bytes": 5 * 10 * 4096 + 3 * 4096 + 7}
    names = asyncio.run(store.build(work, config, traffic, SEED))
    loss = store.Loss(work, names, "disk05")
    lost_dir = os.path.join(work, "store", "disk05") + os.sep
    slots = set()
    for name in names:
        assert loss.apply(name) == 6
        with open(store.meta_path(work, name)) as f:
            ref = json.load(f)
        assert len(ref["parts"]) == 6
        for part in ref["parts"]:
            chunks = part["data"] + part["parity"]
            missing = [i for i, c in enumerate(chunks)
                       if not any(os.path.exists(loc)
                                  for loc in c["locations"])]
            assert len(missing) == 1
            assert all(loc.startswith(lost_dir)
                       for loc in chunks[missing[0]]["locations"])
            slots.add(missing[0])
        # a second loss of the same object loses the same chunks again
        assert loss.apply(name) == 6
    assert len(slots) > 1


def test_repair_gib_s_weights_a_straddling_job_by_its_overlap():
    t0, t1 = 100.0, 110.0
    records = [
        ["resilver", 98.0, 102.0, 200, 4 * GIB, "s/0", None],  # half in
        ["resilver", 103.0, 104.0, 200, 1 * GIB, "s/1", None],  # inside
        ["resilver", 109.0, 113.0, 200, 4 * GIB, "s/2", None],  # a quarter
        ["resilver", 104.0, 105.0, 500, 8 * GIB, "s/3", None],  # failed
        ["resilver", 111.0, 112.0, 200, 8 * GIB, "s/4", None],  # after
        ["put", 104.0, 105.0, 200, 8 * GIB, "o/1", None],  # another op
    ]
    assert run.gib_s(records, "resilver", t0, t1) == pytest.approx(0.4)
    assert run.gib_s(records, "put", t0, t1) == pytest.approx(0.8)


STUB = '''"""A configuration's own reference: the plain one with one address
changed, so a run compared with it cannot be correct."""

import reference


def expected_chunks(body, d, p, chunk):
    out = reference.expected_chunks(body, d, p, chunk)
    out[0][1][0] = "0" * 64
    return out
'''


def test_configuration_names_its_reference_and_tunables(tmp_path, capsys,
                                                        monkeypatch):
    from chunky_bits_tpu.cluster import Cluster

    cell = "d8p3-512k.put-4m"
    root = tiny_root(str(tmp_path), [cell], suffix=".tiny")
    path = os.path.join(root, "benchmark", "configs", "d8p3-512k.tiny.json")
    with open(path) as f:
        config = json.load(f)
    config.update(reference="stub_reference",
                  tunables={"hedge_ms": 7.5, "backend": "numpy"})
    with open(path, "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmark", "stub_reference.py"),
              "w") as f:
        f.write(STUB)
    built = []
    from_obj = Cluster.from_obj.__func__

    def record(cls, obj):
        built.append(from_obj(cls, obj))
        return built[-1]

    monkeypatch.setattr(Cluster, "from_obj", classmethod(record))
    rc = run.main(["--workload", cell + ".tiny", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"], root=root,
                  require_chip=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the stub's one changed address is the one mismatch per object
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["chunk_mismatches"]["value"] == \
        checks["puts_checked"]["value"] >= 1
    assert built and all(c.tunables.hedge_ms == 7.5 for c in built)
    # the backend is the harness's, not the tunables'
    assert {c.tunables.backend for c in built} == {"jax"}


def test_a_configuration_without_tunables_keeps_the_backend_alone(tmp_path):
    config = {"profile": {"data": 2, "parity": 1, "chunk_size": 12},
              "destinations": 3}
    obj = store.cluster_obj(str(tmp_path), config, "native")
    assert obj["tunables"] == {"backend": "native"}
