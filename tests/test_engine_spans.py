"""The serving engine's spans, read back from a real profiler trace.

One tiny MoE wave is served with the profiler off, then again under
``jax.profiler``; the ``.xplane.pb`` is read with
``jax.profiler.ProfileData``.  The spans must nest as documented in
``repro.serve.engine``, count one ``engine.sync`` per blocking read (one
in the prefill, one in each step's commit, one after the last step), and
leave the served tokens as they were.
"""
from __future__ import annotations

import glob
import os
import warnings
from collections import deque

import numpy as np
import pytest

import jax

from repro.configs import base
from repro.serve.engine import Engine, Request

CFG = base.smoke(base.get("kimi_k2_1t_a32b"))
NAMES = {"engine.wave", "engine.prefill", "engine.step", "engine.commit",
         "engine.decode", "engine.sync"}

#: (prompt lengths, max_new per request, max_len): a wave that serves every
#: budget, and one that runs out of cache, so its last step calls no decode
WAVES = {"whole": ([5, 3, 7], [4, 2, 3], 32),
         "truncated": ([5, 3, 7], [6, 2, 3], 10)}


def _wave(lens, max_new):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, CFG.vocab, n)
                    .astype(np.int32), max_new=m)
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _engine_spans(trace_dir):
    """(name, start_ns, end_ns, metadata) of every ``engine.*`` event, by
    start."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     _stats(e) if e.name == "engine.wave" else {})
                    for e in line.events if e.name.startswith("engine.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _stats(event):
    # the stats iterator's type warns that it names no module
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def _inside(child, parents):
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


@pytest.mark.parametrize("case", sorted(WAVES))
def test_spans_of_one_wave(case, tmp_path):
    lens, max_new, max_len = WAVES[case]
    eng = Engine(CFG, slots=3, max_len=max_len)
    plain = _wave(lens, max_new)
    assert eng.serve_wave(plain, deque(), {}) is not None
    traced = _wave(lens, max_new)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert eng.serve_wave(traced, deque(), {}) is not None
    finally:
        jax.profiler.stop_trace()
    assert [r.out for r in traced] == [r.out for r in plain]

    spans = _engine_spans(str(tmp_path))
    assert {s[0] for s in spans} == NAMES   # metadata is not in the name
    by = {n: [s for s in spans if s[0] == n] for n in NAMES}
    plen = max(lens)
    steps = min(max(max_new), max_len - plen)
    truncated = steps < max(max_new)
    assert truncated == (case == "truncated")
    assert [r.truncated for r in traced] == [truncated and m > steps
                                             for m in max_new]

    assert len(by["engine.wave"]) == len(by["engine.prefill"]) == 1
    assert len(by["engine.step"]) == steps
    assert len(by["engine.commit"]) == steps
    assert len(by["engine.decode"]) == steps - truncated
    wave = by["engine.wave"]
    assert wave[0][3] == {"rids": "0 1 2"}
    for name in ("engine.prefill", "engine.step"):
        assert all(_inside(s, wave) for s in by[name])
    prefill = by["engine.prefill"]
    assert all(p[2] <= by["engine.step"][0][1] for p in prefill)
    for k, step in enumerate(by["engine.step"]):
        commit = [c for c in by["engine.commit"] if _inside(c, [step])]
        decode = [d for d in by["engine.decode"] if _inside(d, [step])]
        assert len(commit) == 1
        assert len(decode) == (0 if truncated and k == steps - 1 else 1)
        # one read of the step's whole token array, inside its commit
        syncs = [s for s in by["engine.sync"] if _inside(s, [step])]
        assert len(syncs) == 1 and _inside(syncs[0], commit)
    assert not [s for s in by["engine.sync"]
                if _inside(s, by["engine.decode"])]
    # the prefill's poison read is its one sync
    assert len([s for s in by["engine.sync"] if _inside(s, prefill)]) == 1
    # the wave's decode poison total: one read after the last step
    last = by["engine.step"][-1]
    after = [s for s in by["engine.sync"] if s[1] >= last[2]]
    assert len(after) == 1 and _inside(after[0], wave)
    # and no other: the prefill's, one a step, the wave's
    assert len(by["engine.sync"]) == 1 + steps + 1
