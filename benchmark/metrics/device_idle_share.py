"""1 - (union of device op intervals) / traced window, in %, averaged
over the cell's chips (``benchmark/trace_reduce.py``)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
