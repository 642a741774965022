"""Programs lowered or compiled inside the window, persistent-cache hits
included, counted from ``jax.monitoring`` events while the window is
open.  Set-up warms every shape the traffic uses, so a steady run reads
0."""
SOURCE = "program_counter"
UNIT = "count"


def read(run):
    return run.record.get("compiles")
