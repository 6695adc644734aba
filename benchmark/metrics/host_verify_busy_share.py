"""Share of the host pipeline's workers (``parallel/host_pipeline.py``)
busy with SHA-256 verification of chunks read back over the window, in
%: the ``verify`` stage's busy seconds over threads times the window.
Nothing to read when no chunk was verified."""


def read(w):
    busy = w.counters["verify_busy_s"]
    if not busy:
        return None
    return 100.0 * busy / (w.counters["host_threads"] * w.seconds)
