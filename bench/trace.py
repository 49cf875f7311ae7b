"""Profiler capture and its reduction to device metrics.

:class:`Capture` records a ``jax.profiler`` trace of a window; the
reduction reads the ``.xplane.pb`` it leaves with
``jax.profiler.ProfileData`` and turns it into plain interval lists, and
the functions below them compute, from those lists alone:

- the device's busy time: the union of the intervals in which an operation
  ran, within the window, averaged over the devices used;
- each program's device time: the summed durations of its executions
  (the ``XLA Modules`` line of a device plane), keyed by the jitted
  function's name;
- the longest idle gaps, each named by the innermost host span open in the
  middle of it: the benchmark's annotations (``bench.*``) and, where the
  traced run adds them, the program's own ``obs`` spans (``serve.*``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"  # the host annotation that bounds the traced window


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Tuple[str, float, float]]  # ("program/op", start_ns, end_ns)
    modules: List[Tuple[str, float, float]]  # (program name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host: List[Tuple[str, float, float]]  # bench.* annotations (+ obs spans)

    def window(self) -> Optional[Interval]:
        spans = [(s, e) for n, s, e in self.host if n == WINDOW]
        return spans[-1] if spans else None


def program_name(module: str) -> str:
    """``jit__fused_loop(123)`` -> ``_fused_loop``: the jitted function's
    name, as the program defines it."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...), kind=...`` -> ``fusion.12``."""
    return event.split(" = ", 1)[0].strip().lstrip("%")


def _label_ops(ops, modules):
    """Prefix each operation with the program whose execution holds it."""
    import bisect

    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
        out.append((f"{prog}/{op_name(name)}", s, e))
    return out


def from_profile(pd) -> Trace:
    """Interval lists of a ``ProfileData``: device planes and the host's
    ``bench.*`` annotations."""
    devices: Dict[str, DeviceTrace] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dt = DeviceTrace([], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dt.ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    dt.modules += [
                        (program_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events
                    ]
            if dt.ops or dt.modules:
                dt.ops = _label_ops(dt.ops, dt.modules)
                devices[plane.name] = dt
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (e.name, e.start_ns, e.end_ns)
                    for e in line.events
                    if e.name.startswith("bench.")
                ]
    return Trace(devices, host)


def merge(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Union of the device's operation intervals within the window (the
    programs' intervals where the trace has no operation line)."""
    iv = [(s, e) for _, s, e in (dev.ops or dev.modules)]
    return sum(e - s for s, e in merge(iv, lo, hi))


def program_ns(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device time of each program within the window, averaged over the
    devices that ran any."""
    tot: Dict[str, float] = {}
    for dev in trace.devices.values():
        for name, s, e in dev.modules:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    n = max(1, len(trace.devices))
    return {k: v / n for k, v in tot.items()}


def leaves(ops):
    """The operations that hold no other (a ``while`` or ``conditional``
    spans the operations of its body on the same line)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (name, s, e) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][1] < e and ops[i + 1][2] <= e:
            continue
        out.append((name, s, e))
    return out


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10):
    """The ``k`` innermost operations that took the most device time, in
    seconds."""
    tot: Dict[str, float] = {}
    for dev in trace.devices.values():
        for name, s, e in leaves(dev.ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    n = max(1, len(trace.devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10):
    """The ``k`` longest gaps between device operations within the window,
    each named by the innermost host span open at its middle (``idle``
    where none was), in seconds."""
    gaps = []
    for dev in trace.devices.values():
        busy = merge([(s, e) for _, s, e in (dev.ops or dev.modules)], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        open_ = [
            (hs, n) for n, hs, he in trace.host
            if n != WINDOW and hs <= mid <= he
        ]
        out.append([max(open_)[1] if open_ else "idle", (e - s) / 1e9])
    return out


def reduce(trace: Trace) -> Optional[dict]:
    """Busy and window seconds, per-program device seconds and the
    breakdown of the traced window; None when the trace holds no device
    operation or no window."""
    win = trace.window()
    if win is None or not trace.devices:
        return None
    lo, hi = win
    busy = [busy_ns(d, lo, hi) for d in trace.devices.values()]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "program_s": {k: v / 1e9 for k, v in program_ns(trace, lo, hi).items()},
        "breakdown": {
            "device_ops": top_ops(trace, lo, hi),
            "idle_gaps": idle_gaps(trace, lo, hi),
        },
    }


class Capture:
    """``with Capture() as cap: ...`` traces the enclosed block into a
    temporary directory; ``cap.trace`` holds the interval lists after exit
    and the directory is gone."""

    def __init__(self):
        self.trace: Optional[Trace] = None

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        import jax
        from jax.profiler import ProfileData

        try:
            jax.profiler.stop_trace()
            paths = glob.glob(
                os.path.join(self._dir, "**", "*.xplane.pb"), recursive=True
            )
            if paths:
                self.trace = from_profile(ProfileData.from_file(paths[-1]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
