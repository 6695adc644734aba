"""The per-request phase readers (``benchmark/program_spans.py``) on a
small CPU trace of hand-placed spans
(``benchmark/testdata/cpu-spans.xplane.pb``: ``TraceAnnotation``s opened
and closed at planned times under one profiler session, no program).

The recorded events, ns from the window's start (start, duration):

=====================  ===========  ===========  =========
name                   start        duration     req
=====================  ===========  ===========  =========
gateway.put                 57652    100080349   a
writer.receive            2123287     18149151   a
writer.receive           10171695     19987458   a
writer.encode            35130605     25043521   a
batching.encode          36194387      8043010   (parts 3)
codec.h2d                40106281      4923561
codec.launch             45038357      2092769
codec.collect            47141140      9895622
dest.write               55212632     14846900   a
dest.write               60184122      4941132   a
meta.write               80192764     15018729   a
dest.write               90053233     20185921   a  (ends past its request)
reader.fetch            115174603     10092276   b  (starts before it)
gateway.get             120096003     80078185   b
reader.fetch            130176737     15104936   b
reader.fetch            135154098     15001386   b
reader.reconstruct      150168754     30066682   b
codec.h2d               155119675      3109648
codec.launch            158237606      1049504
codec.collect           159296280      3893036
gateway.get.send        185135067      4942841   b
=====================  ===========  ===========  =========
"""

import os
import shutil
import sys

import pytest

from benchutil import BENCH

sys.path.insert(0, BENCH)

import program_spans  # noqa: E402
import spec  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "cpu-spans.xplane.pb")
MS = 1e-6

# request a: gateway.put [57652, 100138001]
PUT_END = 57652 + 100080349
# the two receives overlap: [2123287, 10171695 + 19987458]
RECEIVE = (10171695 + 19987458) - 2123287
ENCODE = 25043521
# the second write lies inside the first; the third is clipped at the
# request's end
CHUNK_WRITE = 14846900 + (PUT_END - 90053233)
META_WRITE = 15018729
# children's union: receive, encode with the first writes
# [35130605, 55212632 + 14846900], meta with the clipped third write
# [80192764, PUT_END]
PUT_COVERED = (RECEIVE + (55212632 + 14846900 - 35130605)
               + (PUT_END - 80192764))
PUT_UNSPANNED = 100080349 - PUT_COVERED

# request b: gateway.get [120096003, 200174188]
GET_START = 120096003
# the first fetch is clipped at the request's start; the other two
# overlap: [130176737, 135154098 + 15001386]
FETCH = ((115174603 + 10092276) - GET_START
         + (135154098 + 15001386) - 130176737)
RECONSTRUCT = 30066682
# the fetches end 13270 ns before the reconstruct starts
GET_UNSPANNED = 80078185 - (FETCH + RECONSTRUCT + 4942841)

CODEC = (4923561 + 2092769 + 9895622 + 3109648 + 1049504 + 3893036) / 2


@pytest.fixture(scope="module")
def spans():
    return program_spans.read_xplane(TRACE)


def test_requests_and_children(spans):
    assert {r: m for r, (m, _, _) in spans.requests.items()} == {
        "a": "put", "b": "get"}
    assert spans.requests["a"][1:] == (57652, PUT_END)
    assert len(spans.children["a"]) == 7 and len(spans.children["b"]) == 5
    assert [r for r, _, _ in spans.counted("put")] == ["a"]
    assert spans.window_ns > PUT_END


def test_phases_union_and_clip(spans):
    assert spans.phase_ms("put", ("writer.receive",)) == pytest.approx(
        RECEIVE * MS)
    assert spans.phase_ms("put", ("writer.encode",)) == pytest.approx(
        ENCODE * MS)
    assert spans.phase_ms("put", ("dest.write",)) == pytest.approx(
        CHUNK_WRITE * MS)
    assert spans.phase_ms("put", ("meta.write",)) == pytest.approx(
        META_WRITE * MS)
    assert spans.phase_ms("get", ("reader.fetch",)) == pytest.approx(
        FETCH * MS)
    assert spans.phase_ms("get", ("reader.reconstruct",)) == \
        pytest.approx(RECONSTRUCT * MS)
    # a group span carries no req: it is no request's phase
    assert spans.phase_ms("put", ("batching.encode",)) == 0.0


def test_unspanned(spans):
    assert spans.unspanned_ms("put") == pytest.approx(PUT_UNSPANNED * MS)
    assert spans.unspanned_ms("get") == pytest.approx(GET_UNSPANNED * MS)
    # hand numbers: 17.170319 ms and 19.919039 ms
    assert PUT_UNSPANNED == 17170319 and GET_UNSPANNED == 19919039


def test_codec_dispatch(spans):
    assert spans.codec_dispatch_ms() == pytest.approx(CODEC * MS)
    assert CODEC == 12482070


def test_window_and_nothing_to_read():
    """A request whose span starts outside the window does not count; a
    trace without the program's spans reads nothing."""
    events = [(-5, 50, "gateway.put", "x"), (10, 20, "dest.write", "x"),
              (30, 90, "gateway.put", "y"), (40, 95, "dest.write", "y")]
    s = program_spans.Spans(100, events)
    assert [r for r, _, _ in s.counted("put")] == ["y"]
    assert s.phase_ms("put", ("dest.write",)) == pytest.approx(50 * MS)
    empty = program_spans.Spans(100, [])
    assert empty.phase_ms("put", ("dest.write",)) is None
    assert empty.unspanned_ms("get") is None
    assert empty.codec_dispatch_ms() is None
    assert empty.counted_share() is None


def test_counted_share(spans):
    """The counted requests over every request with a span in the
    window: one whose envelope opened before the window (``x``) or
    closed after it (``z``, seen by its child alone) is not counted."""
    assert spans.counted_share() == pytest.approx(100.0)
    events = [(-5, 50, "gateway.put", "x"), (10, 20, "dest.write", "x"),
              (30, 90, "gateway.put", "y"), (40, 95, "dest.write", "y"),
              (60, 80, "writer.receive", "z"), (70, 75, "codec.launch", None)]
    assert program_spans.Spans(100, events).counted_share() == \
        pytest.approx(100.0 / 3)
    assert program_spans.Spans(100, events[-1:]).counted_share() is None


def test_union_length():
    u = program_spans.union_length
    assert u([], 0, 10) == 0.0
    assert u([(2, 4), (3, 6), (8, 12)], 0, 10) == 6
    assert u([(-5, 3), (4, 5)], 0, 10) == 4
    assert u([(1, 9), (2, 3)], 0, 10) == 8


def test_readers_read_their_own_checkout(tmp_path):
    """A reader finds the trace under the checkout it lies in, as
    ``run.py`` leaves it, and nothing where no traced run left one."""
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    tmp_path / "benchmark" / "metrics")
    metrics = str(tmp_path / "benchmark" / "metrics")

    def reader(name):
        return spec._load_reader(spec._reader_path(metrics, name), name)

    assert reader("put_receive_ms")(None) is None
    run_dir = tmp_path / ".bench_work" / "trace" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    shutil.copy(TRACE, run_dir / "host.xplane.pb")
    assert reader("put_receive_ms")(None) == pytest.approx(RECEIVE * MS)
    assert reader("put_chunk_write_ms")(None) == pytest.approx(
        CHUNK_WRITE * MS)
    assert reader("put_unspanned_ms")(None) == pytest.approx(
        PUT_UNSPANNED * MS)
    assert reader("get_reconstruct_ms")(None) == pytest.approx(
        RECONSTRUCT * MS)
    assert reader("codec_dispatch_ms.get")(None) == pytest.approx(
        CODEC * MS)
    assert reader("counted_request_share.put")(None) == pytest.approx(
        100.0)
