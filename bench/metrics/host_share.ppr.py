"""Share of the traced window outside the engine's ``serve.tick`` spans:
the host loop's own time (admission, retirement, the harness)."""

from bench.readers import obs_span_s, traced_window


def read(run):
    busy = obs_span_s(run, "serve.tick")
    win = traced_window(run)
    if busy is None or win is None:
        return None
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
