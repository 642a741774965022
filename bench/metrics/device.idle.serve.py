"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the
window."""
SOURCE = "device_trace"
UNIT = "%"


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share
