#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--seconds 0.001]

In one process, for every seed: the cell's set-up, a short window at the
cell's own load (long enough to finish a wave), and the numbers the cell
compares, as a run reads them.  For the control seeds, the same window is
also read with the configuration's control (the reference one precision
down) in the program's place, through the same checks: such a run has to
come out not correct at the cell's limits.  One JSON line per seed, then,
per number, the largest program reading and the smallest control reading.
The benchmark's own runs never read the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import chip, spec  # noqa: E402
from bench.lib.outcome import Context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.001)
    args = ap.parse_args(argv)
    chip.use_compile_cache()
    cell = spec.load_cell(args.workload)
    devs = chip.require_tpu(cell.chips)
    driver = spec.load_module("drivers", cell.kind)
    limits = cell.limits()
    names = list(limits)
    unlimited = {n: {"limit": math.inf} for n in names}
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    program = {n: [] for n in names}
    control = {n: [] for n in names}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = Context(cell, seed, args.seconds, False, t, devs, unlimited,
                      chip.CompileCounter(), control=seed in controls)
        out = driver.run(ctx)
        got = {c.name: c.value for c in out.checks}
        mine = got
        if ctx.control:
            mine = {n: out.notes["program"][k]
                    for n, k in driver.GAP_CHECKS.items()}
            for n in names:
                control[n].append(got[n])
        for n in names:
            program[n].append(mine[n])
        at_limits = not out.problems and all(
            c.value <= float(limits[c.name]["limit"]) if c.name in limits
            else c.ok for c in out.checks)
        line = {"seed": seed, "control": ctx.control,
                "correct_at_the_cells_limits": at_limits, "checks": got,
                "problems": out.problems, "notes": out.notes,
                "e2e": out.end_to_end, "samples": out.samples,
                "wall_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": cell.name, "numbers": {
        n: {"lower": max(program[n], default=None),
            "upper": min(control[n], default=None)} for n in names},
        "seeds": len(program[names[0]]),
        "control_seeds": len(control[names[0]])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
