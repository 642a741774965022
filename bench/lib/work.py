"""The work of a served model's layers, counted from a configuration's
published widths: the arithmetic that the configurations' own
``kernel_calls`` and ``token_flops`` (``bench/configs/<config>.py``)
share.

Counts follow the published layer, not the program that runs it: what
the program adds (capacity slack, padding, work it throws away) is not
counted.  Operations are multiply-adds, kept as whole numbers so that a
sum of them is exact in any order.
"""
from __future__ import annotations

from typing import Dict, List


def gqa_macs(d: int, heads: int, kv_heads: int, head_dim: int,
             context: int) -> int:
    """One token through grouped-query attention: the query, key, value
    and output projections, and the scores and values over the
    ``context`` positions it attends to."""
    proj = d * heads * head_dim * 2 + d * kv_heads * head_dim * 2
    return proj + 2 * heads * head_dim * context


def moe_macs(d: int, experts: int, ff: int, active: int) -> int:
    """One token through an MoE layer: the router over ``experts``, and
    ``active`` gated experts of width ``ff`` (the routed top-k and the
    shared ones)."""
    return d * experts + 3 * d * ff * active


def head_macs(d: int, vocab: int) -> int:
    """One token's logits."""
    return d * vocab


def spec_dispatch(tokens: int, count: int, *, d: int, experts: int,
                  top_k: int, capacity: int, itemsize: int) -> List[Dict]:
    """``count`` speculative dispatches of ``tokens`` tokens to their
    ``top_k`` experts: one ``spec_gather`` and one ``spec_scatter_add``
    call each, over a buffer of ``capacity`` rows per expert."""
    if not count:
        return []
    shape = dict(requests=tokens * top_k, d=d, itemsize=itemsize,
                 table_rows=experts * capacity)
    return [dict(kernel="spec_gather", count=count, **shape),
            dict(kernel="spec_scatter_add", count=count, **shape)]
