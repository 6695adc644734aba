"""Mean ms a PUT spends with a chunk write in flight: the union of its
``dest.write`` spans, clipped to its ``gateway.put`` span
(``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "put", ("dest.write",))
