"""The ``staging_reuse_share`` reader: the share of the window's staging
blocks that came back from the writer's pool, read from the
``writer.stage.*`` spans, and nothing from a program that writes none."""

import json
import os
import shutil
import sys

import pytest

from benchutil import BENCH, REPO

sys.path.insert(0, BENCH)

import program_spans  # noqa: E402
import spec  # noqa: E402


def _reader(tmp_path):
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    tmp_path / "benchmark" / "metrics", dirs_exist_ok=True)
    metrics = str(tmp_path / "benchmark" / "metrics")
    name = "staging_reuse_share.put"
    return spec._load_reader(spec._reader_path(metrics, name), name)


def test_reads_nothing_without_stage_spans(tmp_path):
    """No traced run, or a trace with PUTs but no ``writer.stage.*`` span
    (a program from before the pool), reads nothing."""
    read = _reader(tmp_path)
    assert read(None) is None
    run_dir = tmp_path / ".bench_work" / "trace" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "testdata", "cpu-spans.xplane.pb"),
                run_dir / "host.xplane.pb")
    assert read(None) is None


@pytest.mark.parametrize("reused, fresh, share", [
    (3, 1, 75.0), (0, 2, 0.0), (5, 0, 100.0)])
def test_share_of_blocks_taken_in_the_window(tmp_path, monkeypatch,
                                             reused, fresh, share):
    """Reused over all blocks taken whose span starts in the window;
    spans before or after it do not count."""
    events = [(10 + i, 11 + i, "writer.stage.reused", "a")
              for i in range(reused)]
    events += [(50 + i, 51 + i, "writer.stage.fresh", "b")
               for i in range(fresh)]
    events += [(-5, -4, "writer.stage.fresh", None),
               (1000, 1001, "writer.stage.fresh", None),
               (0, 900, "gateway.put", "a")]
    spans = program_spans.Spans(1000, events)
    monkeypatch.setattr(program_spans, "load", lambda root: spans)
    assert _reader(tmp_path)(None) == pytest.approx(share)


def test_listed_in_the_put_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "staging_reuse_share.put")
    puts = [w["name"] for w in bench["workloads"] if w["traffic"]
            .startswith("put-")]
    assert entry["workloads"] == puts == [
        "rs10-4-1m.put-256m", "d8p3-512k.put-4m", "d20p6-4m.put-320m"]
    assert (entry["moves"], entry["layer"], entry["unit"]) == (
        "put_gib_s", "gateway/writer", "%")
