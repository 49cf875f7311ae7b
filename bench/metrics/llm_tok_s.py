"""Output tokens delivered to clients in the window per window second."""

from bench.readers import tokens_in_window


def read(run):
    n = tokens_in_window(run)
    return n / run.seconds if n else None
