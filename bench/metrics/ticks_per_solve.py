"""Mean engine ticks from admission to retirement of certified requests."""

from bench.stats import mean


def read(run):
    return mean([r.result.retire_tick - r.result.admit_tick
                 for r in run.in_window()
                 if r.result is not None and r.result.converged])
