"""Device time in collectives over device busy time, in %, averaged over
the cell's chips (``benchmark/trace_reduce.py``)."""


def read(w):
    if w.trace is None:
        return None
    busy = sum(c.busy_s for c in w.trace.chips)
    coll = sum(c.collective_s for c in w.trace.chips)
    return 100.0 * coll / busy if busy > 0 else None
