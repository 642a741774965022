"""The per-layer readers on a record and a trace made up by hand."""
import json
import os

import pytest

from bench.lib import spec
from bench.lib import trace as T

PEAKS = spec.peaks("TPU v5 lite")
CFG = json.load(open(os.path.join(spec.BENCH, "configs",
                                  "kimi_k2_ep24.json")))
CELL = spec.load_cell("kimi_k2_ep24.decode_heavy")


class View:
    def __init__(self, record, reduced):
        self.record, self.reduced = record, reduced
        self.cell, self.peaks, self.cost = CELL, PEAKS, spec.cost


def read(name, record, reduced=None):
    return spec.load_module("metrics", name).read(View(record, reduced))


CALL = dict(requests=256, d=7168, itemsize=2, table_rows=384)


def serve_record():
    waves = [dict(start=0.0, first_call=0.5, end=2.0, decode_calls=3,
                  kernel_calls=[dict(CALL, kernel="spec_gather", count=2),
                                dict(CALL, kernel="spec_scatter_add",
                                     count=2)]),
             dict(start=2.0, first_call=3.5, end=5.0, decode_calls=3,
                  kernel_calls=[dict(CALL, kernel="spec_gather", count=1)]),
             dict(start=11.0, first_call=11.1, end=12.0, decode_calls=1,
                  kernel_calls=[])]
    requests = {0: dict(prompt=4, times=[0.5, 1.0, 1.5]),
                1: dict(prompt=2, times=[9.0, 10.5])}
    return dict(window=(0.0, 10.0), waves=waves, requests=requests,
                compiles=3, config=CFG)


def trace_with(kernel_events):
    dev = [("fusion.1", 0, 4e9)] + kernel_events
    return T.reduce(T.Trace({0: dev}, [("bench.window", 0, 10e9)]), 1)


def test_compiles_are_read_from_the_counter():
    assert read("engine.compiles_in_window", serve_record()) == 3


def test_mfu_by_hand():
    cost = CELL.module().token_flops
    flops = (sum(cost(CFG, c, False) for c in range(1, 5))
             + cost(CFG, 4, True) - cost(CFG, 4, False)
             + cost(CFG, 5, True) + cost(CFG, 6, True)
             + sum(cost(CFG, c, False) for c in range(1, 3))
             + cost(CFG, 2, True) - cost(CFG, 2, False))
    # request 1's second token (10.5 s) is after the window
    assert read("model_step.mfu", serve_record()) == pytest.approx(
        100 * flops / (10.0 * PEAKS["bf16_flops"]))


def test_roofline_reads_bound_over_device_time():
    evs = [(f"spec_gather.{i}", 5e9 + i * 1e6, 1e5) for i in range(3)]
    got = read("spec_gather_roofline.serve", serve_record(), trace_with(evs))
    _, nbytes = spec.cost("spec_gather")(CALL)
    assert got == pytest.approx(
        100 * 3 * (nbytes / PEAKS["hbm_bytes_per_s"]) / 3e-4)


@pytest.mark.parametrize("name,kernel,n", [
    ("spec_gather_roofline.serve", "spec_gather", 2),
    ("spec_gather_roofline.serve", "spec_gather", 4),
    ("spec_scatter_roofline.serve", "spec_scatter_add", 0)])
def test_roofline_fails_when_calls_and_events_disagree(name, kernel, n):
    evs = [(f"{kernel}.{i}", 5e9 + i * 1e6, 1e5) for i in range(n)]
    with pytest.raises(RuntimeError, match="logged"):
        read(name, serve_record(), trace_with(evs))


def test_roofline_reads_nothing_without_calls_or_trace():
    assert read("spec_gather_roofline.serve", serve_record()) is None
    record = dict(serve_record(), waves=[dict(kernel_calls=[])])
    assert read("spec_scatter_roofline.serve", record,
                trace_with([])) is None


def test_idle_share_in_percent():
    r = trace_with([])
    assert read("device.idle.serve", {}, r) == pytest.approx(60.0)
    assert read("device.idle.serve", {}) is None
