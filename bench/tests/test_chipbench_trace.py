"""Reduction of a profiler trace: busy and idle time, kernel time, and
idle gaps named by the host event in each.  One trace is made up here;
the other was recorded on a TPU v5e in a serving window and cut to its
first 200 ms (``data/serve_trace.json``)."""
import os

import numpy as np
import pytest

from bench.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "serve_trace.json")


def made_up() -> T.Trace:
    dev = [("fusion.1", 100, 50), ("fusion.2", 120, 60),   # 100..180
           ("spec_gather.3", 300, 20), ("spec_gather.4", 330, 10),
           ("copy.9", 900, 200)]                           # to 1100
    host = [("bench.window", 0, 1000), ("ReadSyncFlag", 10, 80),
            ("PjitFunction(f)", 5, 95), ("np.asarray", 190, 100),
            ("lower_sharding_computation", 350, 500),
            ("tiny", 400, 5)]
    return T.Trace({0: dev}, host)


def test_made_up_trace_by_hand():
    r = T.reduce(made_up(), 1)
    assert r.window == (0, 1000)
    busy = 80 + 20 + 10 + 100                 # the copy is cut at 1000
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert r.idle_share == pytest.approx(1 - busy / 1000)
    assert r.kernel("spec_gather") == (pytest.approx(30e-9), 2)
    assert r.ops == pytest.approx({"fusion": 110e-9, "spec_gather": 30e-9,
                                   "copy": 100e-9})
    # gaps: 0..100 (ReadSyncFlag overlaps it by 80, PjitFunction by 95),
    # 180..300 (np.asarray, 100), 320..330 (no host event), 340..900
    # (the lowering by 500, the tiny event by 5)
    assert r.gaps == pytest.approx({
        "PjitFunction_f_": 100e-9, "np.asarray": 120e-9,
        "_no_host_event_": 10e-9, "lower_sharding_computation": 560e-9})
    assert r.breakdown()["idle_gaps"][0][0] == "lower_sharding_computation"


def test_more_than_one_window_is_refused():
    tr = made_up()
    tr.host.append(("bench.window", 2000, 10))
    with pytest.raises(ValueError):
        tr.window()


def test_round_trip_through_json():
    tr = made_up()
    assert T.Trace.from_json(tr.to_json()).host == [tuple(e) for e in
                                                    tr.host]


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return T.Trace.from_json(f.read())


def _timeline(events, window):
    lo, hi = int(window[0]), int(window[1])
    on = np.zeros(hi - lo, bool)
    for _, s, d in events:
        a, b = max(int(s), lo), min(int(s + d), hi)
        if b > a:
            on[a - lo:b - lo] = True
    return on


def test_recorded_idle_share_against_a_timeline(recorded):
    r = T.reduce(recorded, 1)
    # nanosecond timeline, rounded to whole nanoseconds
    on = _timeline(recorded.device[0], r.window)
    assert r.busy_s == pytest.approx(on.sum() * 1e-9, rel=1e-4)
    assert 0.0 < r.idle_share < 1.0
    gaps = T.idle_gaps(recorded.device[0], r.window)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert sum(r.gaps.values()) == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-6)


def test_recorded_kernel_time_is_the_sum_of_its_events(recorded):
    r = T.reduce(recorded, 1)
    for k in ("spec_gather", "spec_scatter_add"):
        secs, n = r.kernel(k)
        evs = [e for e in recorded.device[0] if k in e[0]]
        assert n == len(evs) > 0
        assert secs == pytest.approx(sum(e[2] for e in evs) * 1e-9)


def test_recorded_longest_gap_goes_to_the_host_event_covering_most(
        recorded):
    r = T.reduce(recorded, 1)
    gaps = T.idle_gaps(recorded.device[0], r.window)
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    best = max(((min(b, s + d) - max(a, s), -d, n)
                for n, s, d in recorded.host
                if not n.startswith("bench.") and s < b and s + d > a),
               default=None)
    got = T.attribute_gaps([(a, b)], recorded.host)
    want = T.host_name(best[2]) if best and best[0] > 0 else \
        "_no_host_event_"
    assert got == {want: pytest.approx((b - a) * 1e-9)}
