"""Layer 2 of the observability subsystem: structured *event tracing* with a
Chrome-trace exporter (DESIGN.md S18).

A :class:`Tracer` records two event shapes into the same kind of bounded
ring the metrics registry uses:

- **spans** — ``with tracer.span("serve.tick", n=4):`` records a complete
  duration event (begin timestamp + duration, both from
  ``time.perf_counter_ns`` so they are monotonic and immune to wall-clock
  steps);
- **instants** — ``tracer.instant("protocol.certify", tick=12)`` records a
  zero-duration marker.

Both carry free-form ``args`` key/values that land verbatim in the
exported trace, so per-stage message counts, byte volumes, resize extents
etc. are attached to the event that produced them rather than logged out
of band.

While enabled, each span and instant also opens a
``jax.profiler.TraceAnnotation`` of the same name and args, so under an
active ``jax.profiler`` session the program's spans sit on the trace's host
plane, on the same clock as the device's operations.  A span reported
after the fact (:meth:`Tracer.complete`, e.g. a compile's duration) lands
there as a marker at its end that carries its length as the ``dur_ns``
arg.

Export is :meth:`Tracer.chrome_trace`: the Trace Event Format JSON object
(``{"traceEvents": [...]}``) that ``chrome://tracing`` and Perfetto load
directly.  Complete events use phase ``"X"`` with microsecond ``ts``/
``dur``; instants use phase ``"i"``.  Thread ids are mapped to small
stable ints so e.g. the checkpoint writer thread gets its own lane.

Overflow policy matches metrics: when the ring is full new events are
dropped and counted (:attr:`Tracer.dropped`), never blocking the caller.
"""

from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

_PH_SPAN = "X"
_PH_INSTANT = "i"


class Tracer:
    """Ring-buffered span/instant recorder with Chrome-trace export."""

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self._events: List[tuple] = []  # (ph, name, ts_ns, dur_ns, tid, args)
        self.dropped = 0
        self._tids: Dict[int, int] = {}
        self._spans = 0
        self._instants = 0
        self._lock = threading.Lock()  # export-time snapshot only

    # -- hot path ------------------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _push(self, ev: tuple) -> None:
        if len(self._events) >= self.capacity:
            self.dropped += 1
            return
        self._events.append(ev)

    @contextmanager
    def span(self, name: str, **args):
        """Record a complete duration event around the enclosed block.

        Yields the args dict so the body can attach values only known at
        exit (``with tr.span("tick") as sp: ...; sp["n"] = n``) — the dict
        is read when the event is pushed, at exit."""
        if not self.enabled:
            yield None
            return
        ann = TraceAnnotation(name, **args)
        ann.__enter__()
        n_entry = len(args)
        t0 = time.perf_counter_ns()
        try:
            yield args
        finally:
            dur = time.perf_counter_ns() - t0
            if len(args) > n_entry:  # args the body attached at exit
                ann.set_metadata(**dict(list(args.items())[n_entry:]))
            ann.__exit__(None, None, None)
            self._spans += 1
            self._push((_PH_SPAN, name, t0, dur, self._tid(), args or None))

    def complete(self, name: str, dur_ns: int, **args) -> None:
        """Record a span that ended now and lasted ``dur_ns``."""
        if not self.enabled:
            return
        with TraceAnnotation(name, dur_ns=dur_ns, **args):
            pass
        self._spans += 1
        t1 = time.perf_counter_ns()
        self._push((_PH_SPAN, name, t1 - dur_ns, dur_ns, self._tid(), args or None))

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration marker event."""
        if not self.enabled:
            return
        with TraceAnnotation(name, **args):
            pass
        self._instants += 1
        self._push(
            (_PH_INSTANT, name, time.perf_counter_ns(), 0, self._tid(), args or None)
        )

    # -- export ----------------------------------------------------------------

    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def chrome_trace(self, process_name: str = "repro") -> Dict[str, Any]:
        """Trace Event Format object loadable by chrome://tracing / Perfetto.

        Timestamps are microseconds relative to the earliest recorded event
        (Perfetto renders absolute perf_counter epochs poorly)."""
        evs = self.events()
        t0 = min((e[2] for e in evs), default=0)
        out = []
        for ph, name, ts, dur, tid, args in evs:
            rec: Dict[str, Any] = {
                "name": name,
                "ph": ph,
                "ts": (ts - t0) / 1e3,
                "pid": 0,
                "tid": tid,
            }
            if ph == _PH_SPAN:
                rec["dur"] = dur / 1e3
            else:
                rec["s"] = "t"  # thread-scoped instant
            if args:
                rec["args"] = {k: _jsonable(v) for k, v in args.items()}
            out.append(rec)
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 0,
                "args": {"name": process_name},
            }
        ]
        for ident, tid in sorted(self._tids.items(), key=lambda kv: kv[1]):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 0,
                    "tid": tid,
                    "args": {"name": "main" if tid == 0 else f"thread-{tid}"},
                }
            )
        return {"traceEvents": meta + out, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str, process_name: str = "repro") -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(process_name), f)

    # -- read-back --------------------------------------------------------------

    def summary(self) -> Dict[str, int]:
        return {
            "spans": self._spans,
            "instants": self._instants,
            "recorded": len(self._events),
            "dropped": self.dropped,
        }

    def counts(self, prefix: str = "") -> Dict[str, int]:
        """Event counts by name (optionally filtered by prefix)."""
        out: Dict[str, int] = {}
        for ev in self.events():
            name = ev[1]
            if name.startswith(prefix):
                out[name] = out.get(name, 0) + 1
        return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
        self.dropped = 0
        self._spans = 0
        self._instants = 0


class _NullSpan:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)
