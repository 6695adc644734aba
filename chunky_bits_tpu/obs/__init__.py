"""Observability plane: unified metrics registry + request tracing.

A TPU-repo extension (the reference has no metrics surface at all —
src/file/profiler.rs renders one-shot report strings and that is the
whole story): ``obs.metrics`` is the process-wide, thread-safe sink
behind every existing stat source (chunk cache, host pipeline, health
scoreboard, scrub daemon, the gateway access log), exposed as
Prometheus text at gateway ``GET /metrics`` and JSON at ``GET /stats``;
``obs.tracing`` follows one request across the async plane, the host
pipeline's worker threads, and the network fetches, with one span per
layer boundary: into the JAX profiler's trace while a session records,
and into a bounded slowest-N buffer served at ``GET /debug/traces``.

``obs.slo`` is the windowed layer on top of the registry: burn-rate
SLO rules over a bounded snapshot ring, the pending→firing→resolved
alert state machine behind gateway ``GET /alerts``, and the
simulator-verified detection verdicts (sim/scenario.py runs the same
engine in virtual time).

All three modules are stdlib-only and import nothing from the rest of
the package (``obs.slo`` reads time through the clock seam's
stdlib-only implementation half, ``utils/clock.py`` — the same
cycle-hygiene import file/profiler.py uses), so every layer (file/,
parallel/, cluster/, gateway/, sim/) may feed them without import
cycles, and the linter (which must run without a device or
third-party deps) can scan them like any other module.
"""

from chunky_bits_tpu.obs import metrics, tracing  # noqa: F401
