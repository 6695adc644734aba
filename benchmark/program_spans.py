"""Per-request phases of the served path, read from the program's own
spans in the traced run's profile.

The program writes one span per layer boundary into the JAX profiler's
trace while a session is active (``chunky_bits_tpu/obs/tracing.py``):
a request's envelope ``gateway.<method>`` and its children
(``writer.receive``, ``dest.write``, ``reader.fetch``, ...), each
carrying the request's id as the ``req`` arg, plus the codec dispatch
spans (``codec.h2d``, ``codec.launch``, ``codec.collect``).  The trace
is the ``.xplane.pb`` that ``run.py`` writes under
``<checkout>/.bench_work/trace``; each reader passes its own checkout,
derived from its own path, so a rehearsal in another directory reads
its own trace.

* A request counts if its ``gateway.*`` span starts inside the traced
  window.  A span is recorded only if it opens and closes while the
  profiler records, so the counted requests are those that fit whole in
  the window: the sample leans to the shorter requests, and requests
  longer than the window are never counted.  The counted share (the
  counted requests over every request with a span in the window) says
  how much of the window's traffic the per-request means describe.
* A request's time in a phase is the length of the union of its spans
  of that phase, clipped to its own span; a metric is the mean over the
  requests, in ms.
* Its unspanned time is its span minus the union of all its child
  spans (every span with its ``req`` but the envelope itself).
* The codec's host ms per dispatch is the summed length of the
  ``codec.h2d``, ``codec.launch`` and ``codec.collect`` spans over the
  number of ``codec.launch`` spans (a dispatch's three spans run one
  after another on one thread).

A program that writes no such spans (before they existed) gives nothing
to read: every function here returns None.
"""

from __future__ import annotations

import os
from collections import defaultdict

#: span name prefixes the program writes (obs/tracing.py's catalog)
PREFIXES = ("gateway.", "writer.", "meta.", "dest.", "reader.",
            "batching.", "host.", "codec.")
CODEC_DISPATCH = ("codec.h2d", "codec.launch", "codec.collect")

_CACHE: dict = {}


class Spans:
    """The catalog spans of one trace: per request its envelope and its
    children, and the codec spans; times in ns from the window's start."""

    def __init__(self, window_ns: float, events: list) -> None:
        self.window_ns = window_ns
        #: req -> (method, start, end) of its ``gateway.<method>`` span
        self.requests: dict = {}
        #: req -> [(start, end, name)] of its other spans
        self.children: dict = defaultdict(list)
        #: name -> [(start, end)] of every span of that name
        self.by_name: dict = defaultdict(list)
        for start, end, name, req in events:
            self.by_name[name].append((start, end))
            if req is None:
                continue
            if name.count(".") == 1 and name.startswith("gateway."):
                self.requests[req] = (name.split(".", 1)[1], start, end)
            else:
                self.children[req].append((start, end, name))

    def counted(self, method: str) -> list:
        """``(req, start, end)`` of the ``method`` requests whose span
        starts inside the window."""
        return [(req, lo, hi) for req, (m, lo, hi)
                in sorted(self.requests.items(), key=lambda kv: kv[1][1])
                if m == method and 0 <= lo < self.window_ns]

    def phase_ms(self, method: str, names) -> float | None:
        """Mean over the counted requests of the union of their
        ``names`` spans, clipped to the request's span, in ms."""
        reqs = self.counted(method)
        if not reqs:
            return None
        total = sum(union_length(
            [(s, e) for s, e, n in self.children[req] if n in names],
            lo, hi) for req, lo, hi in reqs)
        return total / len(reqs) * 1e-6

    def unspanned_ms(self, method: str) -> float | None:
        """Mean over the counted requests of their span minus the union
        of all their child spans, in ms."""
        reqs = self.counted(method)
        if not reqs:
            return None
        total = sum((hi - lo) - union_length(
            [(s, e) for s, e, _ in self.children[req]], lo, hi)
            for req, lo, hi in reqs)
        return total / len(reqs) * 1e-6

    def counted_share(self) -> float | None:
        """Counted requests, in % of the requests with any span in the
        window (a request whose envelope opened or closed outside the
        profile is seen only by its children's spans)."""
        seen = set(self.requests) | {r for r, c in self.children.items()
                                     if c}
        if not seen:
            return None
        counted = sum(1 for _m, lo, _hi in self.requests.values()
                      if 0 <= lo < self.window_ns)
        return 100.0 * counted / len(seen)

    def codec_dispatch_ms(self) -> float | None:
        """Host ms per codec dispatch over the window."""
        inside = {name: [(s, e) for s, e in self.by_name.get(name, [])
                         if 0 <= s < self.window_ns]
                  for name in CODEC_DISPATCH}
        launches = len(inside["codec.launch"])
        if not launches:
            return None
        spent = sum(e - s for spans in inside.values() for s, e in spans)
        return spent / launches * 1e-6


def union_length(intervals: list, lo: float, hi: float) -> float:
    """Length of the union of ``intervals``, each clipped to
    ``[lo, hi]``."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def read_xplane(path: str) -> Spans:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    env = next((dict(p.stats) for p in planes
                if p.name == "Task Environment"), {})
    window_ns = env.get("profile_stop_time", 0) - env.get(
        "profile_start_time", 0)
    events = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if not name.startswith(PREFIXES):
                    continue
                req = dict(e.stats).get("req")
                events.append((e.start_ns, e.start_ns + e.duration_ns,
                               name, None if req is None else str(req)))
    return Spans(window_ns, events)


def trace_path(root: str) -> str | None:
    """The traced run's ``.xplane.pb`` under ``root``, or None."""
    for dirpath, _dirs, files in os.walk(
            os.path.join(root, ".bench_work", "trace")):
        for f in sorted(files):
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    return None


def load(root: str) -> Spans | None:
    """The spans of the checkout's traced run (read once a run), or
    None when it left no trace."""
    path = trace_path(root)
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_xplane(path)
    return _CACHE[key]


def checkout_of(reader_file: str) -> str:
    """The checkout of a reader at ``<checkout>/benchmark/metrics/``."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


def phase_ms(reader_file: str, method: str, names) -> float | None:
    spans = load(checkout_of(reader_file))
    return None if spans is None else spans.phase_ms(method, names)


def unspanned_ms(reader_file: str, method: str) -> float | None:
    spans = load(checkout_of(reader_file))
    return None if spans is None else spans.unspanned_ms(method)


def counted_share(reader_file: str) -> float | None:
    spans = load(checkout_of(reader_file))
    return None if spans is None else spans.counted_share()


def codec_dispatch_ms(reader_file: str) -> float | None:
    spans = load(checkout_of(reader_file))
    return None if spans is None else spans.codec_dispatch_ms()
