"""Blocking device-to-host reads per decode step: the ``engine.sync``
spans inside ``engine.step`` spans over the ``engine.step`` spans of the
traced window (the engine's own spans, ``bench/lib/spans.py``).  A step
reads its whole token array once; the decode calls' poison counts stay on
the device until one read after the wave's last step, outside any step."""
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
