"""Host ms per codec dispatch: the summed ``codec.h2d``,
``codec.launch`` and ``codec.collect`` spans of the window over its
``codec.launch`` spans (``benchmark/program_spans.py``); ``.put`` in
the PUT cells, ``.get`` in the GET cell."""

import program_spans


def read(w):
    return program_spans.codec_dispatch_ms(__file__)
