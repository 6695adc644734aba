"""Host spans for the traced run, recorded from the benchmark's side.

The program writes nothing into the profiler's trace, so a device idle
gap could not be told apart from what the host did in it.  For a
``--trace 1`` run the calls into a few layers that run on their own
threads are wrapped in ``jax.profiler.TraceAnnotation`` (thread-local,
so only synchronous calls, never coroutines), and the trace reduction
names each idle gap by the span that covers most of it:

* ``host.<stage>``: a host-pipeline job (``parallel/host_pipeline.py``:
  ``hash``, ``encode``, ``stage``, ``verify``);
* ``batching.encode`` / ``batching.reconstruct``: one coalesced group of
  ``ops/batching.py`` on its worker thread.

A stopgap: it patches private methods of the program
(``HostPipeline._run_job``, the batchers' ``_run_group``) from the
benchmark's side, a second tracing path beside ``obs/tracing.py``.  The
``tracing`` issue replaces it with spans that the program writes itself.
"""

from __future__ import annotations

import functools


class _Patches:
    def __init__(self) -> None:
        self._undo: list = []

    def wrap(self, owner, attr: str, name) -> None:
        import jax

        orig = getattr(owner, attr)

        @functools.wraps(orig)
        def traced(*args, **kwargs):
            label = name(*args) if callable(name) else name
            with jax.profiler.TraceAnnotation(label):
                return orig(*args, **kwargs)

        setattr(owner, attr, traced)
        self._undo.append((owner, attr, orig))

    def undo(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()


def annotate() -> _Patches:
    from chunky_bits_tpu.ops import batching
    from chunky_bits_tpu.parallel import host_pipeline

    patches = _Patches()
    patches.wrap(host_pipeline.HostPipeline, "_run_job",
                 lambda _self, job: f"host.{job.stage}")
    patches.wrap(batching.EncodeHashBatcher, "_run_group",
                 "batching.encode")
    patches.wrap(batching.ReconstructBatcher, "_run_group",
                 "batching.reconstruct")
    return patches
