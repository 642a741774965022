"""Blocking device-to-host reads per decode step: the ``engine.sync``
spans inside ``engine.step`` spans over the ``engine.step`` spans of the
traced window (the engine's own spans, ``bench/lib/spans.py``).  Today a
step reads each active row's token and the decode call's poison count."""
from bench.lib import spans

SOURCE = "program_span"
UNIT = "count"


def read(run):
    found = spans.engine(run)
    if found is None:
        return None
    steps = spans.need(found, "engine.step")
    syncs = spans.need(found, "engine.sync")
    return spans.count_inside(syncs, steps) / len(steps)
