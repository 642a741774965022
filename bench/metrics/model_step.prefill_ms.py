"""Mean host time of a wave's prefill: the ``engine.prefill`` spans that
start in the traced window (padding, upload, the prefill, its poison
read and the first argmax), in milliseconds."""
from bench.lib import spans

SOURCE = "program_span"
UNIT = "ms"


def read(run):
    found = spans.engine(run)
    if found is None:
        return None
    prefills = spans.need(found, "engine.prefill")
    return sum(b - a for a, b in prefills) * 1e-6 / len(prefills)
