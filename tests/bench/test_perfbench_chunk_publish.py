"""The ``put_chunk_publish_ms`` reader: the worker-thread part of a PUT's
chunk writes, read from the ``dest.publish`` spans, and nothing from a
program that writes none."""

import os
import shutil
import sys

import pytest

from benchutil import BENCH

sys.path.insert(0, BENCH)

import program_spans  # noqa: E402
import spec  # noqa: E402

MS = 1e-6


def _reader(tmp_path, name):
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    tmp_path / "benchmark" / "metrics", dirs_exist_ok=True)
    metrics = str(tmp_path / "benchmark" / "metrics")
    return spec._load_reader(spec._reader_path(metrics, name), name)


def test_reads_nothing_without_publish_spans(tmp_path):
    """A trace with chunk writes and no ``dest.publish`` (a program from
    before the span) reads nothing, where the chunk write reads."""
    read = _reader(tmp_path, "put_chunk_publish_ms")
    assert read(None) is None  # no traced run at all
    run_dir = tmp_path / ".bench_work" / "trace" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "testdata", "cpu-spans.xplane.pb"),
                run_dir / "host.xplane.pb")
    assert read(None) is None
    assert _reader(tmp_path, "put_chunk_write_ms")(None) > 0


def test_reads_the_union_of_publications_per_put(tmp_path, monkeypatch):
    """Per counted PUT, the union of its ``dest.publish`` spans clipped
    to the request, averaged: publications run in parallel workers."""
    events = [(0, 100, "gateway.put", "a"), (10, 40, "dest.write", "a"),
              (20, 30, "dest.publish", "a"), (25, 35, "dest.publish", "a"),
              (95, 120, "dest.publish", "a"),
              (200, 300, "gateway.put", "b"), (210, 220, "dest.publish", "b"),
              (250, 260, "dest.publish", None)]
    spans = program_spans.Spans(1000, events)
    monkeypatch.setattr(program_spans, "load", lambda root: spans)
    read = _reader(tmp_path, "put_chunk_publish_ms")
    # a: [20, 35] and [95, 100] = 20 ns; b: 10 ns
    assert read(None) == pytest.approx(15 * MS)
