"""Unified telemetry subsystem (DESIGN.md S18).

Three layers, mirroring the collectives/asynchrony architecture:

- :mod:`repro.obs.metrics` — typed Counter/Gauge/Histogram instruments
  over a ring-buffered :class:`MetricsRegistry` drained by a background
  writer thread (flush-only ``jax.block_until_ready`` fencing);
- :mod:`repro.obs.tracing` — span/instant :class:`Tracer` with monotonic
  timestamps and a ``chrome_trace()`` Perfetto exporter;
- :mod:`repro.obs.sinks` — SINKS registry (null / jsonl / csv /
  chrome_trace) selected by ``--telemetry name[:path]`` on both
  launchers.

The process-global instance is **disabled by default**: every hook in
collectives / asynchrony / serving / runtime / checkpoint guards on
:func:`enabled`, so an uninstrumented run pays one attribute load + one
branch per hook (this is the ``--telemetry null`` baseline the CI
overhead gate compares against).  :func:`configure` turns it on:

    from repro import obs
    obs.configure("chrome_trace:out.json")
    ...
    obs.shutdown()     # drain metrics, export trace via the sink

Instrumentation sites use the module-level conveniences::

    with obs.span("serve.tick", n_ticks=k): ...
    obs.instant("protocol.certify", tick=t)
    obs.counter("coll.messages", op="allreduce").add(m)
    obs.gauge("serve.queue_depth").set(depth)

All of them are cheap no-ops while disabled.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .sinks import SINKS, Sink, get_sink, parse_spec, register_sink
from .tracing import _NULL_SPAN, Tracer

__all__ = [
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Tracer",
    "Sink",
    "SINKS",
    "register_sink",
    "get_sink",
    "parse_spec",
    "Telemetry",
    "configure",
    "shutdown",
    "enabled",
    "telemetry",
    "span",
    "instant",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "summary",
    "reset",
]


# jax.monitoring's event for one backend compile (or persistent-cache load)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Telemetry:
    """A registry + tracer + sink bundle. One process-global instance lives
    in this module; tests construct their own to stay isolated.

    While configured it listens to ``jax.monitoring`` and records every
    backend compile as a ``jax.compile`` span (back-dated by the reported
    duration), so a compile that stalls the host names itself in a trace."""

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        sink: Optional[Sink] = None,
    ):
        self.registry = registry or MetricsRegistry()
        self.tracer = tracer or Tracer()
        self.sink = sink
        self.enabled = False
        self._listening = False

    def _on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT and self.enabled:
            self.tracer.complete(
                "jax.compile", int(duration_secs * 1e9),
                fun=kwargs.get("fun_name", ""),
            )

    def _listen(self, on: bool) -> None:
        if on != self._listening:
            if on:
                jax.monitoring.register_event_duration_secs_listener(self._on_duration)
            else:
                jax.monitoring.unregister_event_duration_listener(self._on_duration)
            self._listening = on

    def configure(self, spec: str = "null", background: bool = True) -> "Telemetry":
        """Select a sink by spec and enable recording.  ``background=True``
        starts the metrics writer thread; tests pass False and drive
        :meth:`MetricsRegistry.flush` themselves."""
        self.sink = get_sink(spec)
        self.enabled = True
        self.tracer.enabled = True
        self._listen(True)
        if background:
            self.registry.start(self.sink)
        else:
            self.registry._sink = self.sink
        return self

    def shutdown(self) -> Dict[str, Any]:
        """Stop the writer, drain, hand the tracer to the sink for export,
        and disable. Returns the final pipeline summary."""
        self.registry.stop()
        if self.sink is not None:
            self.sink.close(self.tracer)
        out = self.summary()
        self.enabled = False
        self.tracer.enabled = False
        self._listen(False)
        return out

    def summary(self) -> Dict[str, Any]:
        """Pipeline health for embedding in other summaries (e.g.
        ``ServeEngine.summary()['telemetry']``)."""
        tr = self.tracer.summary()
        mx = self.registry.summary()
        return {
            "enabled": self.enabled,
            "spans": tr["spans"],
            "instants": tr["instants"],
            "events_dropped": tr["dropped"],
            "metrics_recorded": mx["recorded"],
            "metrics_dropped": mx["dropped"],
            "sink": self.sink.name if self.sink is not None else None,
        }


_GLOBAL = Telemetry()


def telemetry() -> Telemetry:
    """The process-global telemetry instance."""
    return _GLOBAL


def configure(spec: str = "null", background: bool = True) -> Telemetry:
    return _GLOBAL.configure(spec, background=background)


def shutdown() -> Dict[str, Any]:
    return _GLOBAL.shutdown()


def enabled() -> bool:
    return _GLOBAL.enabled


# -- module-level conveniences: the instrumentation-site API.  Each is a
# guarded forward onto the global instance and a no-op while disabled. ------


def span(name: str, **args):
    if not _GLOBAL.enabled:
        return _NULL_SPAN
    return _GLOBAL.tracer.span(name, **args)


def instant(name: str, **args) -> None:
    if _GLOBAL.enabled:
        _GLOBAL.tracer.instant(name, **args)


def counter(name: str, **labels) -> Counter:
    return _GLOBAL.registry.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _GLOBAL.registry.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    return _GLOBAL.registry.histogram(name, **labels)


def snapshot() -> Dict[str, Any]:
    return _GLOBAL.registry.snapshot()


def summary() -> Dict[str, Any]:
    return _GLOBAL.summary()


def reset() -> None:
    """Swap in a fresh disabled global — used between benches in
    ``benchmarks/run.py`` (one trace artifact per bench) and by tests."""
    global _GLOBAL
    try:
        _GLOBAL.registry.stop()
    except Exception:
        pass
    _GLOBAL._listen(False)
    _GLOBAL = Telemetry()


_reset_for_tests = reset
