"""What a driver is handed, and what it hands back."""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .spec import Cell


@dataclasses.dataclass
class Ctx:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                       # perf_counter() at process start
    devices: list
    require_kernel: bool = True     # False only in CPU tests
    trace_dir: Optional[Path] = None
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)
    save_trace: Optional[Path] = None   # keep the recorded trace here
    t_chips: Optional[float] = None     # perf_counter() once JAX found chips


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the run is correct only if
    every value is at or under its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]           # end-to-end metrics by name
    work: Dict[str, object]         # counters the per-layer readers use
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    trace: object = None            # lib.trace.Trace of the window, traced runs
    notes: List[Tuple[str, object]] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0
