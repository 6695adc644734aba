"""Share in % of the window's requests that the per-request span
metrics count: those whose ``gateway.*`` span opened and closed inside
the traced window, over every request with a span in it
(``benchmark/program_spans.py``).  Requests longer than the window are
never counted, so the ``put_*`` / ``get_*`` means describe the shorter
requests; this share says how many.  ``.put`` in the PUT cells, ``.get``
in the GET cell."""

import program_spans


def read(w):
    return program_spans.counted_share(__file__)
