#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The configuration's ``kind`` picks the driver (``bench/drivers``), which
sets up, measures for ``--seconds`` and checks what the timed path
produced against the configuration's plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.

Standard output ends with the sample counts behind every tail, then one
JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), and last ``checks``: every
number compared, beside its limit.  Standard error ends with the same
comparisons.  Without a TPU, or with fewer chips than the cell asks
for, the run prints no result and exits with code 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import chip, spec  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402
from bench.lib.outcome import Context  # noqa: E402


class View:
    """What a per-layer reader is given."""

    def __init__(self, cell, outcome, reduced, peaks, seconds):
        self.cell = cell
        self.record = outcome.record
        self.reduced = reduced
        self.peaks = peaks
        self.seconds = seconds
        self.cost = spec.cost


def read_per_layer(cell, view) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m.name)
        if reader.SOURCE != m.source or reader.UNIT != m.unit:
            raise spec.SpecError(
                f"reader of {m.name} declares {reader.SOURCE}/{reader.UNIT}"
                f", BENCHMARK.json says {m.source}/{m.unit}")
        value = reader.read(view)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def run(args, check_chip=chip.require_tpu, cell=None, limits=None):
    """One run: the result line and the driver's outcome (raises without
    a chip).  Tests hand in a cell, its limits and a stand-in for the
    chip check."""
    cell = cell or spec.load_cell(args.workload)
    devs = check_chip(cell.chips)
    peaks = spec.peaks(devs[0].device_kind)
    ctx = Context(cell, args.seed, float(args.seconds), bool(args.trace),
                  T_START, devs, limits or cell.limits(),
                  chip.CompileCounter())
    driver = spec.load_module("drivers", cell.kind)
    reduced = None
    if ctx.trace:
        ctx.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        outcome = driver.run(ctx)
        if ctx.trace:
            files = chip.trace_files(ctx.trace_dir)
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, got {files}")
            reduced = tracing.reduce(tracing.load(files[0]), cell.chips)
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    device = dict(chip.device_info(devs),
                  memory_peak_bytes=outcome.memory_peak_bytes)
    if ctx.trace:
        metrics = read_per_layer(cell, View(cell, outcome, reduced, peaks,
                                            ctx.seconds))
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m.name not in outcome.end_to_end:
                raise spec.SpecError(f"the {cell.kind} driver reports no "
                                     f"{m.name}")
            metrics[m.name] = {"value": outcome.end_to_end[m.name],
                               "unit": m.unit}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if ctx.trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = outcome.check_line()
    return result, outcome


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    chip.use_compile_cache()
    try:
        result, outcome = run(args)
    except chip.NoChip as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    print("samples behind the tails: " + ", ".join(
        f"{k}={v}" for k, v in outcome.samples.items()), flush=True)
    if outcome.notes:
        print("readings: " + json.dumps(outcome.notes), flush=True)
    for p in outcome.problems:
        print(f"problem: {p}", flush=True)
    print(json.dumps(result), flush=True)
    print("\n".join(f"check {k}: {v['value']!r} (limit {v['limit']!r})"
                    for k, v in result["checks"].items()),
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
