"""Kernel and model costs against counts made by hand, and the Kimi
configuration's own counts against the fixed ones the benchmark kept
before each configuration counted its own work."""
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


KIMI = spec.load_module("configs", "kimi_k2_ep24")
KIMI_CFG = json.load(open(os.path.join(spec.BENCH, "configs",
                                       "kimi_k2_ep24.json")))


def test_model_step_cost_by_hand():
    cfg, cost = KIMI_CFG, KIMI.token_flops
    d, hd, ff = 7168, 112, 2048
    proj = d * 64 * hd * 2 + d * 8 * hd * 2
    moe = d * 16 + 3 * d * ff * (8 + 1)
    per_layer = proj + 2 * 64 * hd * 100 + moe
    assert cost(cfg, 100, False) == 2 * 4 * per_layer
    assert cost(cfg, 100, True) - cost(cfg, 100, False) == 2 * d * 20480
    # one more attended position costs the scores and values of it
    assert cost(cfg, 101, False) - cost(cfg, 100, False) == pytest.approx(
        2 * 4 * 2 * 64 * hd)


def _fixed_token_flops(cfg, context, head):
    """The per-token count the benchmark used before each configuration
    counted its own: GQA attention and an MoE layer in every layer."""
    a = cfg["assumed"]
    d, hd = cfg["hidden_size"], a["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff = cfg["moe_intermediate_size"]
    experts = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    proj = d * h * hd * 2 + d * kv * hd * 2
    attn = 2 * h * hd * context
    moe = d * cfg["n_routed_experts"] + 3 * d * ff * experts
    macs = cfg["num_hidden_layers"] * (proj + attn + moe)
    if head:
        macs += d * cfg["vocab_size"]
    return 2.0 * macs


def _fixed_kernel_calls(cfg, b, plen, decode_calls):
    """The wave's kernel calls as the serve driver logged them before each
    configuration counted its own: one gather and one scatter per layer
    per engine call."""
    a = cfg["assumed"]
    layers, experts = cfg["num_hidden_layers"], cfg["n_routed_experts"]
    top_k = cfg["num_experts_per_tok"]
    calls = []
    for n_tok, count in ((b * plen, layers), (b, layers * decode_calls)):
        if not count:
            continue
        cap = KIMI.capacity(n_tok, experts, top_k, a["capacity_factor"])
        shape = dict(requests=n_tok * top_k, d=cfg["hidden_size"],
                     itemsize=2 if a["dtype"] == "bfloat16" else 4,
                     table_rows=experts * cap)
        calls += [dict(kernel="spec_gather", count=count, **shape),
                  dict(kernel="spec_scatter_add", count=count, **shape)]
    return calls


@pytest.mark.parametrize("head", [False, True])
def test_kimi_token_flops_are_the_fixed_count(head):
    for context in range(1, 1025):
        assert KIMI.token_flops(KIMI_CFG, context, head) == \
            _fixed_token_flops(KIMI_CFG, context, head)


@pytest.mark.parametrize("rows,plen,decode_calls",
                         [(32, 512, 511), (4, 12, 7), (1, 1, 0)])
def test_kimi_kernel_calls_are_the_fixed_list(rows, plen, decode_calls):
    assert KIMI.kernel_calls(KIMI_CFG, rows, plen, decode_calls) == \
        _fixed_kernel_calls(KIMI_CFG, rows, plen, decode_calls)
