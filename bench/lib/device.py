"""The chip a run stands on, and the guards that refuse a run off it.

A run reports nothing unless JAX finds the accelerator the cell asks for,
the Pallas kernels would be compiled for it (not interpreted), the device is
in the table of peaks, every Pallas-mode program holds its Mosaic kernel
(``tpu_custom_call``) and no division site fell back to the jnp twin.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import List, Optional

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class Refused(Exception):
    """The run cannot stand for the chip: exit non-zero, print no result."""


def load_peaks(device_kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise Refused(f"device kind {device_kind!r} is not in {path.name}; "
                      f"known: {sorted(table)}")
    return table[device_kind]


def chips(n: int, platform: str = "tpu"):
    """The first ``n`` devices, or Refused when JAX finds fewer of
    ``platform``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise Refused(f"JAX found no {platform.upper()} "
                      f"(platform {devs[0].platform!r})")
    if len(devs) < n:
        raise Refused(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def require_compiled_kernels() -> None:
    from repro.kernels import ops

    if ops.INTERPRET:
        raise Refused("Pallas kernels would run in interpret mode")


def check_kernel(compiled, what: str) -> str:
    """The compiled program's text, or Refused without a Mosaic kernel."""
    text = compiled.as_text()
    if "tpu_custom_call" not in text:
        raise Refused(f"{what}: no tpu_custom_call in the compiled program")
    return text


def program_bytes(compiled) -> int:
    """What one device holds while ``compiled`` runs: its arguments, its
    outputs (less those aliased to arguments) and its temporaries, from
    the compiler's memory analysis (per device on a mesh)."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def memory_peak_bytes(devices, programs=()) -> Optional[int]:
    """The fullest device's peak: the larger of the runtime's
    ``peak_bytes_in_use`` and what the largest timed program holds while
    it runs. The TPU runtime's counter leaves a program's temporaries
    out, so a program whose temporaries dwarf its arguments would read
    as a nearly empty chip from the counter alone."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    peaks += [program_bytes(c) for c in programs]
    return max(peaks) if peaks else None


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class FallbackSpy:
    """Records every operand the Pallas-mode guard turns away.

    ``division_modes`` sends an operand to the jnp twin when
    ``kernels.ops.pallas_applicable`` refuses it; inside this context each
    refusal (at trace time) is kept in ``refused``.
    """

    def __init__(self):
        from repro.kernels import ops

        self.ops = ops
        self.real = ops.pallas_applicable
        self.refused: List[tuple] = []

    def __enter__(self):
        def spy(x):
            ok = self.real(x)
            if not ok:
                self.refused.append((tuple(x.shape), str(x.dtype)))
            return ok

        self.ops.pallas_applicable = spy
        return self

    def __exit__(self, *exc):
        self.ops.pallas_applicable = self.real
        return False


class CallSpy:
    """Counts calls of ``module.name`` while active (the function is
    looked up on the module at call time, so the spy sees them)."""

    def __init__(self, module, name: str):
        self.module, self.name = module, name
        self.real = getattr(module, name)
        self.calls = 0

    def __enter__(self):
        def spy(*a, **k):
            self.calls += 1
            return self.real(*a, **k)

        setattr(self.module, self.name, spy)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.real)
        return False


class CompileCounter:
    """Counts JAX's tracing and backend-compile events (persistent-cache
    loads included) from the moment it is created."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")
    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        import jax

        self.events: List[tuple] = []
        if CompileCounter._installed is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
        CompileCounter._installed = self

    @staticmethod
    def _listen(event, duration, **kwargs):
        me = CompileCounter._installed
        if me is not None and event in CompileCounter.EVENTS:
            me.events.append((time.perf_counter(), event, duration))

    def count(self, t0: float, t1: float, event: str = EVENTS[0]) -> int:
        return sum(1 for t, e, _ in self.events if t0 <= t <= t1 and e == event)
