"""p95 over the requests sent in the window of first delivery - due time;
one that got no first token waits until the end of the drain."""

from bench.readers import p95, waits_ms


def read(run):
    return p95(waits_ms(run, lambda r: r.first))
