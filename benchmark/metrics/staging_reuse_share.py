"""Share in % of the staging blocks that PUTs' read loops took in the
window and got back from the writer's staging pool (``file/writer.py``,
``StagingPool``): the ``writer.stage.reused`` spans over those and the
``writer.stage.fresh`` ones, each counted where it starts inside the
traced window (``benchmark/program_spans.py``).  A program that writes
neither span reads nothing."""

import program_spans


def read(w):
    spans = program_spans.load(program_spans.checkout_of(__file__))
    if spans is None:
        return None
    taken = {source: sum(1 for start, _end
                         in spans.by_name.get(f"writer.stage.{source}", [])
                         if 0 <= start < spans.window_ns)
             for source in ("reused", "fresh")}
    total = taken["reused"] + taken["fresh"]
    return 100.0 * taken["reused"] / total if total else None
