"""The serving engine's own spans in a traced window.

``repro.serve.engine`` marks its work with ``jax.profiler``
annotations named ``engine.*`` (``engine.wave``, ``engine.prefill``,
``engine.step``, ``engine.commit``, ``engine.decode``, ``engine.sync``);
they are host events of the trace, on the device trace's clock.  A
program that marks nothing (an engine from before the spans) reads None
in every metric here.  A trace that holds engine spans but not the one a
metric reads, or whose prefills and decode calls differ in number from
the waves and calls the driver recorded, fails the run with the span's
name: a renamed or moved span must not read as a number.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as T

PREFIX = "engine."
Interval = Tuple[float, float]


def engine(run) -> Optional[Dict[str, List[Interval]]]:
    """Every engine span that starts in the traced window, cut to it,
    by name; None without a trace or without any engine span."""
    if run.reduced is None:
        return None
    window = run.reduced.window
    out: Dict[str, List[Interval]] = {}
    for n, s, d in T.clip([e for e in run.reduced.trace.host
                           if e[0].startswith(PREFIX)
                           and window[0] <= e[1] <= window[1]], window):
        out.setdefault(n, []).append((s, s + d))
    if not out:
        return None
    waves = run.record["waves"]
    for name, want in (("engine.prefill", len(waves)),
                       ("engine.decode",
                        sum(w["decode_calls"] for w in waves))):
        got = len(out.get(name, ()))
        if got != want:
            raise RuntimeError(f"{name}: {got} spans in the traced window, "
                               f"the driver recorded {want}")
    return {n: sorted(v) for n, v in out.items()}


def need(spans: Dict[str, List[Interval]], name: str) -> List[Interval]:
    """The spans called ``name``; an error where there are none."""
    if not spans.get(name):
        raise RuntimeError(f"{name}: no span in the traced window")
    return spans[name]


def count_inside(children: Sequence[Interval],
                 parents: Sequence[Interval]) -> int:
    """How many of ``children`` lie wholly inside one of ``parents``
    (sorted, disjoint)."""
    starts = [p[0] for p in parents]
    n = 0
    for a, b in children:
        i = bisect.bisect_right(starts, a) - 1
        n += i >= 0 and b <= parents[i][1]
    return n


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def chip0_ops(run) -> List[T.Event]:
    """The device operations of the first chip the cell used."""
    return run.reduced.trace.device[min(run.reduced.trace.device)]
