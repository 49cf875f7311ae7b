"""Device time of the admission (prefill scan) program over the traced
window; 0 where the trace holds no admission."""

from bench.readers import share_of_window


def read(run):
    if not run.trace_summary:
        return None
    return share_of_window(run, "_admit") or 0.0
