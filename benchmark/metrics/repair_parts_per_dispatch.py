"""Parts per decode dispatch over the window, across every decode batcher
(a repair makes one per file, which ``reconstruct_parts_per_dispatch``
cannot see): the deltas of the registry's
``cb_batch_parts_total{op="reconstruct"}`` over
``cb_batch_dispatches_total{op="reconstruct"}`` (``obs/metrics.py``)."""


def read(w):
    n = w.counters["reconstruct_batch_dispatches"]
    return w.counters["reconstruct_batch_parts"] / n if n else None
