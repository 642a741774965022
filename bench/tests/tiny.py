"""A tiny cell for the CPU tests: the serving configuration at toy sizes,
run through the real driver with the chip check skipped."""
from __future__ import annotations

import copy
import json
import os

from bench.lib import chip, spec
from bench.lib.outcome import Context

KIMI_TINY = dict(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, num_hidden_layers=2,
                 n_routed_experts=4, num_experts_per_tok=2,
                 moe_intermediate_size=32, vocab_size=256)


def serve_cell(traffic: dict | None = None) -> spec.Cell:
    cfg = spec.load_json(os.path.join(spec.BENCH, "configs",
                                      "kimi_k2_ep24.json"))
    cfg = copy.deepcopy(cfg)
    cfg.update(KIMI_TINY)
    cfg["assumed"] = dict(cfg["assumed"], head_dim=16)
    tr = dict(kind="serve", loop="closed", clients=8, slots=4, max_len=24,
              prompt_tokens=dict(dist="uniform", lo=3, hi=12),
              output_tokens=dict(dist="uniform", lo=2, hi=8),
              cycle_waves=2, size_seed=0)
    tr.update(traffic or {})
    real = spec.load_cell("kimi_k2_ep24.decode_heavy")
    return spec.Cell("tiny.serve", 1, "kimi_k2_ep24", cfg, "tiny", tr,
                     real.end_to_end, real.per_layer)


def context(cell: spec.Cell, limits: dict, seed: int = 7,
            seconds: float = 0.5) -> Context:
    import jax
    return Context(cell, seed, seconds, False, 0.0, jax.devices()[:1],
                   limits, chip.CompileCounter())


def run(cell: spec.Cell, limits: dict, **kw):
    ctx = context(cell, limits, **kw)
    return spec.load_module("drivers", cell.kind).run(ctx)


def dumps(outcome) -> str:
    return json.dumps(outcome.check_line())
