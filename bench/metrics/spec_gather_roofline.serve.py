"""``spec_gather``'s share of its roofline in the serve cells: the bound
time of the calls the traced window made, from
``bench/costs/spec_gather.py`` and the shapes the driver logged per call,
over the kernel's device time in the trace (``bench/lib/roofline.py``)."""
from bench.lib import roofline

SOURCE = "device_trace"
UNIT = "%"


def read(run):
    return roofline.share(run, "spec_gather")
