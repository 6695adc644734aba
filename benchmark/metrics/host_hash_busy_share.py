"""Share of the host pipeline's workers (``parallel/host_pipeline.py``)
busy with SHA-256 jobs over the window, in %: the ``hash`` stage's busy
seconds over threads times the window."""


def read(w):
    threads = w.counters["host_threads"]
    return 100.0 * w.counters["hash_busy_s"] / (threads * w.seconds)
