"""Mean ms a PUT spends waiting for parity and digests: the union of
its ``writer.encode`` spans (submit to the encode batcher until the
results are back: queue, group, codec dispatch, hash join), clipped to
its ``gateway.put`` span (``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "put", ("writer.encode",))
