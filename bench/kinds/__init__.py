"""One module per kind of configuration (``bench/kinds/<kind>.py``):
each builds the program under test from the seed, warms it up, drives it
through the measured window and checks its outputs, and returns a
:class:`RunData` from which the metric readers compute every number."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class RunData:
    config: dict
    traffic: dict
    seconds: float
    setup_s: float
    t0: float  # window start (host clock)
    t_end: float  # window end
    t_drained: float  # end of the drain after it
    recs: Dict[int, Any]  # serve_loop.Rec by request index
    memory_peak_bytes: int
    correct: bool
    attempted: int
    failed: int
    checks: Dict[str, dict]  # compared number -> {"value", "limit"}
    tracing: Any = None  # serve_loop.Tracing of a --trace 1 run
    trace_summary: Optional[dict] = None  # trace.reduce() of it
    lag_s: List[float] = dataclasses.field(default_factory=list)
    peaks: Optional[dict] = None
    reference: Any = None  # the configuration's reference module (LLM kinds)

    def in_window(self):
        """Records of the requests due in the window (all were sent)."""
        return [r for r in self.recs.values() if r.due <= self.t_end]


def chip_peaks(devices) -> Optional[dict]:
    """The chip's published peaks (None on a device the table lacks)."""
    from bench.peaks import peaks

    try:
        return peaks(devices[0].device_kind)
    except KeyError:
        return None


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def free_device_memory() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


@contextlib.contextmanager
def frozen_heap():
    """Collect once, then keep what set-up left out of the collector until
    the window and its drain are over, as servers do after start-up: a
    full collection of set-up's objects inside the window took 145 ms on a
    TPU v5e host, against ~9 ms for all the window's other collections."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def checks_entry(value, limit) -> dict:
    return {"value": value, "limit": limit}
