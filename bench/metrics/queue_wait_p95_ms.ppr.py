"""p95 over the requests sent in the window of admission start - submission,
by the engine's stamps; one never admitted waits until the end of the
drain."""

from bench.program import queue_waits_ms
from bench.readers import p95


def read(run):
    w = queue_waits_ms(run)
    return p95(w) if w is not None else None
