"""Readers of what the program records about itself: the engine's request
stamps and its ``obs`` spans.

The traced run's spans are on the trace's clock in ``Trace.host`` (besides
the benchmark's ``bench.*`` annotations), where ``serve_loop.Tracing``
puts them at the window's close.
"""

from __future__ import annotations

from typing import List, Optional

from bench import trace as trace_lib


def queue_waits_ms(run) -> Optional[List[float]]:
    """Admission start - submission in ms, by the engine's stamps
    (``_t_admit``, ``_t_submit``), for every request sent in the window; one
    never admitted waits until the end of the drain.  None where the program
    stamps no admission."""
    if not any(hasattr(r.req, "_t_admit") for r in run.recs.values()):
        return None
    return [(getattr(r.req, "_t_admit", run.t_drained) - r.req._t_submit) * 1e3
            for r in run.in_window()]


def idle_under_spans_s(run) -> Optional[float]:
    """Device idle time in the traced window while one of the program's
    spans is open, averaged over the devices; None without a trace or
    without program spans in it."""
    tr = run.tracing.trace if run.tracing is not None else None
    win = tr.window() if tr is not None else None
    if win is None or not tr.devices:
        return None
    lo, hi = win
    spans = trace_lib.merge(
        [(s, e) for n, s, e in tr.host if not n.startswith("bench.")], lo, hi)
    if not spans:
        return None
    tot = 0.0
    for dev in tr.devices.values():
        busy = trace_lib.merge([(s, e) for _, s, e in (dev.ops or dev.modules)],
                               lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        tot += overlap(idle, spans)
    return tot / len(tr.devices) / 1e9


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot
