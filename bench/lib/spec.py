"""Discovery: everything a run needs, found by the names in
``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's file
says which driver (``kind``) runs it.  Per-layer metrics are readers in
``bench/metrics/<name>.py``, kernel costs are ``bench/costs/<kernel>.py``,
correctness limits are ``bench/limits/<workload>.json`` and the chip's
peaks are ``bench/peaks.json``, keyed by the device kind JAX reports.  A
name with no file, or a device with no peaks, is an error, never a
default.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(RuntimeError):
    """The benchmark's files do not describe the run that was asked for."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _file(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"{kind} name {name!r} is not a benchmark name")
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file for {name!r} (looked for {path})")
    return path


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = _file(kind, name, ".py")
    mod_name = "bench_" + kind + "_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    source: str
    workloads: List[str] | None
    moves: str | None = None


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    @property
    def kind(self) -> str:
        return self.config["kind"]

    def limits(self) -> Dict[str, Any]:
        return load_json(_file("limits", self.name, ".json"))


def _metrics(entries, cell: str, end_to_end: bool) -> List[Metric]:
    out = []
    for m in entries:
        wl = m.get("workloads")
        if wl is not None and cell not in wl:
            continue
        out.append(Metric(m["name"], m["unit"], m["source"], wl,
                          None if end_to_end else m["moves"]))
    return out


def load_cell(workload: str, bench_json: str | None = None) -> Cell:
    spec = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; BENCHMARK.json "
                        f"has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
    if not os.path.isfile(cfg_path):
        raise SpecError(f"config file {cfg_path} is missing")
    config = load_json(cfg_path)
    traffic = load_json(_file("traffic", w["traffic"], ".json"))
    if traffic.get("kind") != config.get("kind"):
        raise SpecError(f"traffic {w['traffic']!r} is for "
                        f"{traffic.get('kind')!r} runs, config "
                        f"{w['config']!r} is {config.get('kind')!r}")
    cell = Cell(workload, int(w["chips"]), w["config"], config,
                w["traffic"], traffic,
                _metrics(spec["end_to_end"], workload, True),
                _metrics(spec["per_layer"], workload, False))
    for m in cell.per_layer:       # every reader must exist before a run
        _file("metrics", m.name, ".py")
    _file("drivers", cell.kind, ".py")
    return cell


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def cost(kernel: str):
    """The ``cost`` function of ``bench/costs/<kernel>.py``."""
    return load_module("costs", kernel).cost
