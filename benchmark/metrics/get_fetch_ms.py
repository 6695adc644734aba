"""Mean ms a GET spends with a chunk fetch in flight (read and
verification): the union of its ``reader.fetch`` spans, clipped to its
``gateway.get`` span (``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "get", ("reader.fetch",))
