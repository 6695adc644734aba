"""Mean ms a PUT spends waiting on its body bytes: the union of its
``writer.receive`` spans, clipped to its ``gateway.put`` span
(``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "put", ("writer.receive",))
