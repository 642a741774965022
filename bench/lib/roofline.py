"""A kernel's share of its roofline over the traced window."""
from __future__ import annotations


def share(run, kernel: str):
    """The least time the chip needs for the calls of ``kernel`` that the
    driver logged (per call, the larger of operations over the bf16 peak
    and bytes over the bandwidth, from ``bench/costs/<kernel>.py``), over
    the kernel's device time in the trace, in percent.  None where the
    run made no call and the trace holds none; a trace whose calls differ
    in number from the logged ones is an error, as the logged shapes then
    no longer describe the work timed."""
    if run.reduced is None:
        return None
    seconds, events = run.reduced.kernel(kernel)
    calls = [c for w in run.record["waves"] for c in w["kernel_calls"]
             if c["kernel"] == kernel]
    logged = sum(c["count"] for c in calls)
    if not events and not logged:
        return None
    if events != logged or not seconds:
        raise RuntimeError(f"{kernel}: {events} calls in the trace, "
                           f"{logged} logged by the driver")
    cost = run.cost(kernel)
    bound = 0.0
    for c in calls:
        ops, nbytes = cost(c)
        bound += c["count"] * max(ops / run.peaks["bf16_flops"],
                                  nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
