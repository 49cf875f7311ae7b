"""p99 of submission time - due time of the open loop's requests."""

from bench.stats import percentile


def read(run):
    return percentile([x * 1e3 for x in run.lag_s], 99)
