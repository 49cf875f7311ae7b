"""Share of the traced window in which the device is idle while one of the
program's spans is open: the chip waiting on the engine's host work."""

from bench.program import idle_under_spans_s


def read(run):
    t = idle_under_spans_s(run)
    s = run.trace_summary
    if t is None or not s or s["window_s"] <= 0:
        return None
    return 100.0 * t / s["window_s"]
