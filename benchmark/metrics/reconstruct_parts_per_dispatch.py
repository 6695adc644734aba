"""Parts served per decode dispatch of the shared ``ReconstructBatcher``
(``ops/batching.py``) over the window."""


def read(w):
    n = w.counters["reconstruct_dispatches"]
    return w.parts_get / n if n else None
