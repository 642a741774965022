"""The model step's share of the chip's bf16 peak: the operations the
forward pass needs for every token processed inside the window (each
prompt token of a request whose first token came in the window, and each
later token committed in it), over the window and the peak.  Operations
per token come from ``bench/costs/model_step.py``."""
SOURCE = "host_clock"
UNIT = "%"


def read(run):
    cost = run.cost("model_step")
    cfg = run.record["config"]
    lo, hi = run.record["window"]
    flops = 0.0
    for r in run.record["requests"].values():
        ts, p = r["times"], r["prompt"]
        if ts and lo <= ts[0] <= hi:
            flops += sum(cost(cfg, c, False) for c in range(1, p + 1))
            flops += cost(cfg, p, True) - cost(cfg, p, False)
        flops += sum(cost(cfg, p + j, True)
                     for j, t in enumerate(ts[1:], 1) if lo <= t <= hi)
    if not flops:
        return None
    return 100.0 * flops / ((hi - lo) * run.peaks["bf16_flops"])
