"""Mean ms a PUT spends publishing its file reference (with the fsync):
its ``meta.write`` span, clipped to its ``gateway.put`` span
(``benchmark/program_spans.py``)."""

import program_spans


def read(w):
    return program_spans.phase_ms(__file__, "put", ("meta.write",))
