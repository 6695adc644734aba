"""Mean ms of a GET's ``gateway.get`` span that none of its child spans
covers: what the spans do not explain (``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.unspanned_ms(__file__, "get")
