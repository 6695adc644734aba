"""Parts acknowledged per encode dispatch of the shared
``EncodeHashBatcher`` (``ops/batching.py``) over the window."""


def read(w):
    n = w.counters["encode_dispatches"]
    return w.parts_put / n if n else None
