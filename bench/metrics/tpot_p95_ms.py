"""p95 over requests with deliveries at two or more times in the window of
(last - first delivery) / tokens delivered after the first."""

from bench.readers import p95, tpot_ms


def read(run):
    return p95(tpot_ms(run))
