"""The measured window: offers a schedule's requests to a ``ServeEngine``
on the host clock and records what the client sees.

A token reaches the client when the ``engine.step()`` call that produced it
returns: that is when the engine hands control, and its outputs, back.  So
each request's record holds its due time, its send time and its
deliveries ``(time, tokens so far)``; every end-to-end and per-layer number
is computed from these records afterwards.

With a ``Tracing`` object, the profiler (and the program's own ``obs``
spans) record a sub-window of the measured one, bounded by a
``bench.window`` annotation; the steps inside it are recorded with their
tick counts.  The traffic files put it at the end of the window, so the
stall of writing and reading the trace falls after the last submission.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from bench import trace as trace_lib

clock = time.perf_counter


@dataclasses.dataclass
class Rec:
    index: int
    req: object
    due: float  # when the request was due (host clock)
    sent: float  # when it was submitted
    deliveries: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    done: Optional[float] = None  # when its retirement was seen
    result: object = None

    def deliver(self, t: float, n: int) -> None:
        if n > (self.deliveries[-1][1] if self.deliveries else 0):
            self.deliveries.append((t, n))

    @property
    def first(self) -> Optional[float]:
        return self.deliveries[0][0] if self.deliveries else None


@dataclasses.dataclass
class Step:
    start: float
    end: float
    ticks: int


class Tracing:
    """Profiler over ``[start_s, stop_s)`` of the window (seconds after its
    start), switched on and off between engine steps."""

    def __init__(self, start_s: float, stop_s: float):
        self.start_s, self.stop_s = start_s, stop_s
        self.capture = None
        self.window = None  # (start, end) host clock
        self.steps: List[Step] = []
        self.obs_spans: List[tuple] = []
        self.trace = None  # trace_lib.Trace, once closed
        self._ann = None

    def poll(self, elapsed: float) -> None:
        if self.capture is None and self.window is None and elapsed >= self.start_s:
            from repro import obs

            obs.configure("null", background=False)
            self.capture = trace_lib.Capture().__enter__()
            self._ann = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
            self._ann.__enter__()
            self.window = (clock(), None)
        elif self.capture is not None and elapsed >= self.stop_s:
            self.close()

    @property
    def on(self) -> bool:
        return self.capture is not None

    def close(self) -> None:
        if self.capture is None:
            return
        from repro import obs

        self._ann.__exit__(None, None, None)
        self.window = (self.window[0], clock())
        self.obs_spans = obs.telemetry().tracer.events()
        obs.shutdown()
        obs.reset()
        self.capture.__exit__(None, None, None)
        self.trace = self.capture.trace
        self.capture = None
        if self.trace is not None:
            add_obs_spans(self.trace, self.obs_spans, self.window[0])


def add_obs_spans(trace, spans, window_start_s: float) -> None:
    """Put the program's ``obs`` spans (host clock, ns) onto the trace's
    clock, by the window's start on each, so that they name the host's work
    in idle gaps."""
    win = trace.window()
    if win is None:
        return
    off = win[0] - window_start_s * 1e9
    trace.host += [(ev[1], ev[2] + off, ev[2] + ev[3] + off)
                   for ev in spans if ev[0] == "X"]


def drive(
    engine,
    schedule,
    make_request: Callable[[int], object],
    seconds: float,
    *,
    needs_done: bool,
    drain_s: float = 60.0,
    tracing: Optional[Tracing] = None,
):
    """Offer ``schedule`` for ``seconds``, then keep stepping (sending
    nothing new) until every request sent has its first token, or, with
    ``needs_done``, has completed; at most ``drain_s`` more.

    Returns ``(records by index, t0, t_end, t_drained)``.
    """
    recs: Dict[int, Rec] = {}
    by_id: Dict[int, Rec] = {}
    t0 = clock()
    t_end = t0 + seconds
    seen = set(engine.results)
    closed = False  # no more requests are sent

    def satisfied(r: Rec) -> bool:
        return r.done is not None if needs_done else r.first is not None

    while True:
        now = clock()
        el = now - t0
        if not closed:
            # everything due in the window is sent, late if need be
            closed = now >= t_end
            with jax.profiler.TraceAnnotation("bench.submit"):
                for i, due in schedule.due_now(min(el, seconds)):
                    req = make_request(i)
                    engine.submit(req)
                    r = Rec(i, req, due=t0 + due, sent=clock())
                    recs[i] = by_id[req.id] = r
                    if hasattr(schedule, "sent"):
                        schedule.sent(i, r.sent - t0)
        elif all(satisfied(r) for r in recs.values()) or now > t_end + drain_s:
            break
        if tracing is not None:
            # after the submissions: closing the trace stalls the loop
            # while the profiler writes and the trace is read
            tracing.poll(el)
        if engine.queue or engine.active.any():
            tick0, ts = engine.tick, clock()
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
            t = clock()
            if tracing is not None and tracing.on:
                tracing.steps.append(Step(ts, t, engine.tick - tick0))
            with jax.profiler.TraceAnnotation("bench.observe"):
                for s, req in enumerate(engine.slot_req):
                    if req is not None and req.id in by_id:
                        by_id[req.id].deliver(t, int(engine._new_tokens[s]))
                for rid in set(engine.results) - seen:
                    seen.add(rid)
                    r = by_id[rid]
                    res = engine.results[rid]
                    r.deliver(t, int(res.n_tokens))
                    r.done, r.result = t, res
                    if hasattr(schedule, "completed"):
                        schedule.completed(r.index, t - t0)
        else:
            nd = schedule.next_due()
            wake = t_end if nd is None or closed else min(t0 + nd, t_end)
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(wake - clock(), 0.05)))
    if tracing is not None:
        tracing.close()
    return recs, t0, t_end, clock()
