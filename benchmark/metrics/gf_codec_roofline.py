"""The codec programs' share of their roofline, in %: the least time
their shapes need on this chip (``benchmark/work.py``) over their device
time in the trace (the union of their own ops' intervals), over all the
cell's chips.  Nothing to read when no
codec program ran in the traced window."""


def read(w):
    if w.trace is None:
        return None
    spent = sum(c.codec_s for c in w.trace.chips)
    least = sum(c.codec_least_s for c in w.trace.chips)
    return 100.0 * least / spent if spent > 0 else None
