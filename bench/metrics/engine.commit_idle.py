"""Share of the traced window in which the first chip sat idle while the
engine read the step's tokens back: the chip's idle gaps
(``bench/lib/trace.py``) inside ``engine.commit`` spans, over the
window."""
from bench.lib import spans
from bench.lib import trace as T

SOURCE = "device_trace"
UNIT = "%"


def read(run):
    found = spans.engine(run)
    if found is None:
        return None
    commits = spans.need(found, "engine.commit")
    window = run.reduced.window
    idle = spans.overlap_ns(T.idle_gaps(spans.chip0_ops(run), window),
                            commits)
    return 100.0 * idle / (window[1] - window[0])
