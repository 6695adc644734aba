"""What ``store.warm`` dispatches, recorded through the coder it asks
for: the five ``rs`` cells warm exactly the calls they warmed before the
warm-up followed the profile's code, and a ``pm-msr`` profile warms its
own programs."""

import sys

import pytest

from benchutil import BENCH, REPO

sys.path.insert(0, BENCH)

import spec  # noqa: E402
import store  # noqa: E402

MIB = 1 << 20
ALL_BUT_0 = tuple(range(1, 14))

#: per cell: (method, input rows, shard length, present, wanted) with
#: the largest batch, each warmed at batch sizes 1 up to it, in order
PINNED = {
    "rs10-4-1m.put-256m": [
        (("encode_batch", 10, MIB), 51),
        (("encode_batch", 10, 629146), 8)],
    "d8p3-512k.put-4m": [
        (("encode_batch", 8, 512 * 1024), 20)],
    "rs10-4-1m.get-degraded-10m": [
        (("reconstruct_batch_picked", 10, MIB,
          (1, 2, 4, 5, 6, 8, 9, 10, 11, 13), (0, 3, 7)), 20)],
    "d20p6-4m.put-320m": [
        (("encode_batch", 20, 4 * MIB), 4)],
    "rs10-4-1m.resilver-1disk": [
        (("reconstruct_batch_picked", 10, MIB, ALL_BUT_0, (0,)), 10),
        (("reconstruct_batch_picked", 10, 629146, ALL_BUT_0, (0,)), 1)],
}


class Recorder:
    """A coder that records each call and dispatches nothing."""

    def __init__(self, real, calls):
        self.real = real
        self.calls = calls

    def __getattr__(self, name):
        return getattr(self.real, name)

    def encode_batch(self, x):
        self.calls.append(("encode_batch", x.shape))

    def reconstruct_batch_picked(self, x, present, wanted):
        self.calls.append(("reconstruct_batch_picked", x.shape,
                           tuple(present), tuple(wanted)))

    def project_batch(self, failed, content):
        self.calls.append(("project_batch", content.shape, failed))

    def repair_batch(self, failed, helpers, projections):
        self.calls.append(("repair_batch", projections.shape, failed,
                           tuple(helpers)))


def warmed(monkeypatch, config, traffic):
    """The calls ``store.warm`` makes, as ``[(key, largest batch)]``
    runs; host zeros (a backend off the device) stay untouched."""
    from chunky_bits_tpu.ops import backend

    real = backend.get_coder
    calls = []
    monkeypatch.setattr(backend, "get_coder",
                        lambda d, p, be=None, code="rs": Recorder(
                            real(d, p, "numpy", code), calls))
    n = store.warm(None, dict(config, backend="numpy"), traffic)
    assert n == len(calls)
    runs = []
    for method, shape, *rest in calls:
        key = (method, *shape[1:], *rest)
        if runs and runs[-1][0] == key and runs[-1][1] == shape[0] - 1:
            runs[-1][1] = shape[0]
        else:
            assert shape[0] == 1
            runs.append([key, 1])
    return [tuple(r) for r in runs]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_rs_cells_warm_the_pinned_calls(monkeypatch, cell):
    c = spec.load(REPO, cell)
    assert warmed(monkeypatch, c.config, c.traffic) == PINNED[cell]


def test_scrub_warms_the_plan_programs_by_code(monkeypatch):
    traffic = {"op": "scrub", "clients": 8, "store_objects": 16,
               "store_object_bytes": 256 * MIB}
    rs = {"profile": {"data": 10, "parity": 4, "chunk_size": 20}}
    # the decode plan reads 10 helpers and rebuilds the other 4 rows
    assert warmed(monkeypatch, rs, traffic) == [
        (("reconstruct_batch_picked", 10, MIB, tuple(range(4, 14)),
          (0, 1, 2, 3)), 1),
        (("reconstruct_batch_picked", 10, 629146, tuple(range(4, 14)),
          (0, 1, 2, 3)), 1)]
    msr = {"profile": {"data": 5, "parity": 4, "chunk_size": 20,
                       "code": "pm-msr"}}
    # 256 MiB is 51 parts of 5 MiB and a 1 MiB tail, whose shards are
    # 209716 bytes (1 MiB / 5, up to a multiple of alpha = 4): a helper
    # projects [1, S] to S / 4, and 8 projections combine to a chunk
    helpers = tuple(range(1, 9))
    assert warmed(monkeypatch, msr, traffic) == [
        (("project_batch", MIB, 0), 1),
        (("repair_batch", 8, MIB // 4, 0, helpers), 1),
        (("project_batch", 209716, 0), 1),
        (("repair_batch", 8, 209716 // 4, 0, helpers), 1)]


def test_pm_msr_put_and_get_warm_their_own_coder(monkeypatch):
    msr = {"profile": {"data": 5, "parity": 4, "chunk_size": 20,
                       "code": "pm-msr"}}
    put = {"op": "put", "clients": 2, "object_bytes": 5 * MIB + 10,
           "warm_batches": 2}
    assert warmed(monkeypatch, msr, put) == [
        (("encode_batch", 5, MIB), 2), (("encode_batch", 5, 4), 2)]
    get = {"op": "get", "clients": 2, "store_objects": 2,
           "store_object_bytes": 5 * MIB, "lost_slots": [0, 6],
           "warm_batches": 2}
    assert warmed(monkeypatch, msr, get) == [
        (("reconstruct_batch_picked", 5, MIB, (1, 2, 3, 4, 5, 7, 8),
          (0,)), 2)]
