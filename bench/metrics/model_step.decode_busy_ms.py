"""Device time of one decode step: the time in which some operation ran
on the first chip (the union of its operations) inside ``engine.step``
spans, over the ``engine.step`` spans of the traced window, in
milliseconds."""
from bench.lib import spans
from bench.lib import trace as T

SOURCE = "device_trace"
UNIT = "ms"


def read(run):
    found = spans.engine(run)
    if found is None:
        return None
    steps = spans.need(found, "engine.step")
    busy = T.union(T.clip(spans.chip0_ops(run), run.reduced.window))
    return spans.overlap_ns(busy, steps) * 1e-6 / len(steps)
