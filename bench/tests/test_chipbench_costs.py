"""Kernel and model costs against counts made by hand."""
import json
import os

import pytest

from bench.lib import spec


def test_gather_cost_by_hand():
    # 4 requests of one bf16 row of 8 elements: read 16 B, write 16 B,
    # index 4 B each
    call = dict(requests=4, d=8, itemsize=2, table_rows=64)
    assert spec.cost("spec_gather")(call) == (0.0, 4 * (16 + 16 + 4))


def test_scatter_cost_by_hand():
    # each request reads its value row, reads and writes one table row
    # and its index, and makes one add per element
    call = dict(requests=3, d=128, itemsize=4, table_rows=8)
    assert spec.cost("spec_scatter_add")(call) == (
        3 * 128, 3 * (3 * 512 + 4))


def test_costs_ignore_the_table_size():
    small = dict(requests=5, d=16, itemsize=2, table_rows=8)
    big = dict(small, table_rows=1 << 20)
    for k in ("spec_gather", "spec_scatter_add"):
        assert spec.cost(k)(small) == spec.cost(k)(big)


def test_model_step_cost_by_hand():
    cfg = json.load(open(os.path.join(spec.BENCH, "configs",
                                      "kimi_k2_ep24.json")))
    cost = spec.cost("model_step")
    d, hd, ff = 7168, 112, 2048
    proj = d * 64 * hd * 2 + d * 8 * hd * 2
    moe = d * 16 + 3 * d * ff * (8 + 1)
    per_layer = proj + 2 * 64 * hd * 100 + moe
    assert cost(cfg, 100, False) == 2 * 4 * per_layer
    assert cost(cfg, 100, True) - cost(cfg, 100, False) == 2 * d * 20480
    # one more attended position costs the scores and values of it
    assert cost(cfg, 101, False) - cost(cfg, 100, False) == pytest.approx(
        2 * 4 * 2 * 64 * hd)
