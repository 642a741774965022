"""What a driver is handed, and what it hands back."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .spec import Cell


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float               # host clock at process start
    devs: Any                    # the chips the cell runs on
    limits: Dict[str, Any]       # bench/limits/<workload>.json
    counter: Any = None          # lib.chip.CompileCounter
    trace_dir: Optional[str] = None
    control: bool = False        # control in the program's place


@dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    end_to_end: Dict[str, float]
    samples: Dict[str, int]          # the count behind every tail
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    record: Dict[str, Any]           # host-side spans and counters
    problems: List[str] = field(default_factory=list)
    notes: Dict[str, Any] = field(default_factory=dict)  # diagnostics

    @property
    def correct(self) -> bool:
        return not self.problems and all(c.ok for c in self.checks)

    def check_line(self) -> Dict[str, Dict[str, float]]:
        return {c.name: {"value": c.value, "limit": c.limit}
                for c in self.checks}

