"""Reduction of a profiler trace to the numbers the metrics read.

:func:`load` turns the profiler's ``.xplane.pb`` into a plain
:class:`Trace`: the device operations of each chip (the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane), every host event, and the
benchmark's own ``bench.*`` spans, all in nanoseconds on the trace's one
clock.  Everything after that is arithmetic on intervals, so it can be
checked on a small recorded trace (``bench/tests/data``) without a chip.
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    device: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def spans(self, name: str) -> List[Tuple[float, float]]:
        """(start, end) of every benchmark span called ``name``."""
        return sorted((s, s + d) for n, s, d in self.host if n == name)

    def window(self) -> Tuple[float, float]:
        """The traced window: the one ``bench.window`` span."""
        w = self.spans(SPAN_PREFIX + "window")
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} bench.window spans")
        return w[0]

    def to_json(self) -> str:
        return json.dumps({"device": {str(k): v for k, v in
                                      self.device.items()},
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls({int(k): [tuple(e) for e in v]
                    for k, v in d["device"].items()},
                   [tuple(e) for e in d["host"]])


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            tr.device[int(m.group(1))] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
    tr.host.sort(key=lambda e: e[1])
    return tr


def clip(events: Sequence[Event], window: Tuple[float, float]
         ) -> List[Event]:
    """Events cut to the part of them inside ``window``."""
    lo, hi = window
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals covered by ``events``."""
    out: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event], window: Tuple[float, float]) -> float:
    """Nanoseconds of ``window`` in which some device operation ran."""
    return sum(b - a for a, b in union(clip(events, window)))


def idle_gaps(events: Sequence[Event], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """The parts of ``window`` in which no device operation ran."""
    gaps, t = [], window[0]
    for a, b in union(clip(events, window)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def op_name(name: str) -> str:
    """A device operation's name without its instance number (the trace
    names an operation by its HLO line, ``%name.3 = type op(...)``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def host_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name)[:64]


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   host: Sequence[Event]) -> Dict[str, float]:
    """Seconds of idle device time by what the host was doing: each gap
    goes to the host event that overlaps it most (the shorter one on a
    tie), leaving out the benchmark's own spans."""
    starts = [g[0] for g in gaps]
    ends = [g[1] for g in gaps]
    best: List[Tuple[float, float, str]] = [(0.0, 0.0, "")] * len(gaps)
    for n, s, d in host:
        if n.startswith(SPAN_PREFIX) or d <= 0:
            continue
        e = s + d
        i = bisect.bisect_right(ends, s)
        while i < len(gaps) and starts[i] < e:
            ov = min(e, ends[i]) - max(s, starts[i])
            if ov > best[i][0] or (ov == best[i][0] and ov > 0
                                   and d < best[i][1]):
                best[i] = (ov, d, n)
            i += 1
    out: Dict[str, float] = {}
    for (a, b), (ov, _, n) in zip(gaps, best):
        key = host_name(n) if ov > 0 else "_no_host_event_"
        out[key] = out.get(key, 0.0) + (b - a) * 1e-9
    return out


def top(totals: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


#: operations whose event spans the operations of their body
CONTAINERS = ("while", "conditional", "call")


def op_seconds(events: Sequence[Event], window: Tuple[float, float]
               ) -> Dict[str, float]:
    """Device seconds inside ``window`` by operation name; a loop or call
    is left out, its body's operations are counted."""
    out: Dict[str, float] = {}
    for n, _, d in clip(events, window):
        k = op_name(n)
        if k not in CONTAINERS:
            out[k] = out.get(k, 0.0) + d * 1e-9
    return out


def kernel_events(events: Sequence[Event], window: Tuple[float, float],
                  kernel: str) -> List[Event]:
    """Device events of the Pallas kernel ``kernel`` inside ``window``
    (the kernel's jitted function name is its operation's name)."""
    return [e for e in clip(events, window) if kernel in op_name(e[0])]


@dataclass
class Reduced:
    """What the per-layer readers see of a trace."""
    window: Tuple[float, float]
    chips: int
    busy_s: float               # mean over the chips used
    window_s: float
    ops: Dict[str, float]       # device seconds by op name, all chips
    gaps: Dict[str, float]      # idle seconds by host event, chip 0
    trace: Trace

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> Tuple[float, int]:
        """(device seconds, calls) of a kernel over all chips."""
        evs = [e for dev in self.trace.device.values()
               for e in kernel_events(dev, self.window, name)]
        return sum(d for _, _, d in evs) * 1e-9, len(evs)

    def breakdown(self) -> Dict:
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def reduce(tr: Trace, chips: int) -> Reduced:
    window = tr.window()
    used = sorted(tr.device)[:chips]
    if not used:
        raise ValueError("the trace holds no TPU device plane")
    busy = [busy_ns(tr.device[c], window) * 1e-9 for c in used]
    ops: Dict[str, float] = {}
    for c in used:
        for k, v in op_seconds(tr.device[c], window).items():
            ops[k] = ops.get(k, 0.0) + v
    gaps = attribute_gaps(idle_gaps(tr.device[used[0]], window), tr.host)
    return Reduced(window, len(used), sum(busy) / len(busy),
                   (window[1] - window[0]) * 1e-9, ops, gaps, tr)
