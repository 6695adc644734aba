"""Program spans in the JAX profiler's trace (obs/tracing.py).

One gateway PUT and one degraded GET (a data chunk of every part
removed) run under a CPU profiler session in a child process
(tests/tracing_worker.py), and the ``.xplane.pb`` it leaves is read
here: every catalog span of the two paths is there, carrying its
request's ``req``; every child span lies inside its request's; and the
CPU's XLA ops of the codec programs lie between the ``codec.launch``
that enqueued them and the ``codec.collect`` that waited for them — one
clock for the program's spans and the device's work.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: spans of one request, each carrying its req
PUT_SPANS = {"gateway.put", "gateway.put.admit", "writer.receive",
             "writer.encode", "batching.encode.queue", "host.stage",
             "host.stage.queue", "dest.write", "dest.publish",
             "meta.write"}
GET_SPANS = {"gateway.get", "meta.read", "reader.fetch", "host.verify",
             "reader.reconstruct", "batching.reconstruct.queue",
             "gateway.get.send"}
#: spans serving many requests: a batcher group (parts) and the codec
#: dispatches and hash jobs under it, none carrying a req
SHARED_SPANS = {"batching.encode", "batching.reconstruct", "codec.h2d",
                "codec.launch", "codec.collect", "codec.hash_join",
                "host.hash"}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    import jax

    work = str(tmp_path_factory.mktemp("traced"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "tracing_worker.py"),
         work], env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    events = []
    lines = 0
    for plane in jax.profiler.ProfileData.from_file(out["xplane"]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines += 1
                for e in line.events:
                    stats = dict(e.stats)
                    stats["line"] = lines
                    events.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name, stats))
    return out, events


def _requests(events):
    return {stats["req"]: (name, lo, hi) for lo, hi, name, stats in events
            if name in ("gateway.put", "gateway.get")}


def test_put_and_degraded_get_emit_the_catalog(trace):
    out, events = trace
    reqs = _requests(events)
    assert sorted(m for m, _, _ in reqs.values()) == ["gateway.get",
                                                      "gateway.put"]
    by_req = {}
    for lo, hi, name, stats in events:
        if "req" in stats:
            by_req.setdefault(stats["req"], set()).add(name)
    put_req = next(r for r, (m, _, _) in reqs.items() if m == "gateway.put")
    get_req = next(r for r, (m, _, _) in reqs.items() if m == "gateway.get")
    assert PUT_SPANS <= by_req[put_req], PUT_SPANS - by_req[put_req]
    assert GET_SPANS <= by_req[get_req], GET_SPANS - by_req[get_req]
    names = {name for _, _, name, _ in events}
    assert SHARED_SPANS <= names, SHARED_SPANS - names
    for lo, hi, name, stats in events:
        if name in SHARED_SPANS:
            assert "req" not in stats, (name, stats)
        if name in ("batching.encode", "batching.reconstruct"):
            assert stats["parts"] >= 1 and stats["bytes"] > 0
    envelope = {stats["req"]: stats for _, _, name, stats in events
                if name in ("gateway.put", "gateway.get")}
    assert envelope[put_req]["bytes"] == out["bytes"]
    assert envelope[put_req]["status"] == 200
    assert envelope[get_req]["bytes"] == out["bytes"]
    writes = [s for _, _, n, s in events
              if n == "dest.write" and s.get("req") == put_req]
    # 3 parts of d+p = 5 chunks each
    assert len(writes) == 15


def test_each_chunk_publication_lies_inside_its_write(trace):
    """``dest.publish`` is opened in the worker thread that publishes a
    chunk, off the event loop's line, and carries its request's ``req``
    there: one per chunk write, each inside a ``dest.write`` of its
    request."""
    _, events = trace
    writes = [(lo, hi, s) for lo, hi, n, s in events if n == "dest.write"]
    publishes = [(lo, hi, s) for lo, hi, n, s in events
                 if n == "dest.publish"]
    assert len(publishes) == len(writes) >= 15
    for lo, hi, stats in publishes:
        inside = [w for w in writes if w[0] <= lo and hi <= w[1]
                  and w[2].get("req") == stats.get("req")]
        assert inside, (lo, hi, stats)
        assert all(w[2]["line"] != stats["line"] for w in inside)


def test_the_traced_put_stages_in_the_warm_puts_block(trace):
    """The untraced warm PUT of the same geometry gave its staging block
    back, so the traced PUT's one ``writer.stage.*`` span is
    ``writer.stage.reused``, carrying its request's ``req``."""
    _, events = trace
    put_req = next(r for r, (m, _, _) in _requests(events).items()
                   if m == "gateway.put")
    stages = [(name, stats.get("req")) for _, _, name, stats in events
              if name.startswith("writer.stage.")]
    assert stages == [("writer.stage.reused", put_req)]


def test_child_spans_lie_inside_their_request(trace):
    _, events = trace
    reqs = _requests(events)
    children = 0
    for lo, hi, name, stats in events:
        req = stats.get("req")
        if req is None or name in ("gateway.put", "gateway.get"):
            continue
        if req not in reqs:
            continue  # the worker's own metadata read, outside a request
        _, rlo, rhi = reqs[req]
        assert rlo <= lo and hi <= rhi, (name, lo, hi, rlo, rhi)
        children += 1
    assert children > 30


def test_codec_ops_lie_inside_their_dispatch(trace):
    """The shared clock: the CPU's XLA ops of the encode and decode
    programs are events of the same trace, and each lies inside the
    window of a dispatch, from its ``codec.launch`` (which enqueues the
    program: JAX dispatches asynchronously, on the CPU too) to the end
    of the ``codec.collect`` that waits for it on the same thread."""
    _, events = trace
    launches = [(lo, s["line"]) for lo, _, name, s in events
                if name == "codec.launch"]
    collects = sorted((lo, hi, s["line"]) for lo, hi, name, s in events
                      if name == "codec.collect")
    windows = []
    for lo, line in launches:
        end = next(hi for clo, hi, cline in collects
                   if cline == line and clo >= lo)
        windows.append((lo, end))
    ops = [(lo, hi, name) for lo, hi, name, stats in events
           if stats.get("hlo_module") == "jit_gf_apply_einsum"]
    assert len(ops) >= 3 * len(windows) > 0
    for lo, hi, name in ops:
        assert any(wlo <= lo and hi <= whi for wlo, whi in windows), (
            name, lo, hi)


def test_spans_without_a_sink_leave_jax_unimported():
    code = (
        "import sys\n"
        "from chunky_bits_tpu.obs import tracing\n"
        "from chunky_bits_tpu.parallel.host_pipeline import HostPipeline\n"
        "with tracing.span('dest.write', bytes=1) as s:\n"
        "    s.end(status=200)\n"
        "assert tracing.span('gateway.get') is s\n"
        "assert not tracing.profiling()\n"
        "pipe = HostPipeline(threads=1)\n"
        "assert pipe.submit('hash', lambda: 7).wait() == 7\n"
        "pipe.close()\n"
        "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("CHUNKY_BITS_TPU_TRACE_SLOW_MS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"
