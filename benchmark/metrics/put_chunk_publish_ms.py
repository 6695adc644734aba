"""Mean ms a PUT spends with a chunk publication running in its worker
thread: the union of its ``dest.publish`` spans, clipped to its
``gateway.put`` span (``benchmark/program_spans.py``).  The rest of
``put_chunk_write_ms`` is a chunk write's wait on the event loop and the
thread pool.  A program that writes no ``dest.publish`` span reads
nothing."""

import program_spans


def read(w):
    spans = program_spans.load(program_spans.checkout_of(__file__))
    if spans is None or not spans.by_name.get("dest.publish"):
        return None
    return spans.phase_ms("put", ("dest.publish",))
