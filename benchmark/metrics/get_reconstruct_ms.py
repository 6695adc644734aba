"""Mean ms a GET spends awaiting the decode batcher: the union of its
``reader.reconstruct`` spans, clipped to its ``gateway.get`` span
(``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "get",
                                  ("reader.reconstruct",))
