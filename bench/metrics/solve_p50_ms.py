"""Median over the requests due in the window of certified-solution time -
due time; one never certified waits until the end of the drain."""

from bench.readers import solve_waits_ms
from bench.stats import percentile


def read(run):
    return percentile(solve_waits_ms(run), 50)
