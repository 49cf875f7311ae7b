"""Model operations of the window's real tokens (prompts admitted and
outputs delivered, padding excluded) per second, over the chip's bf16
peak."""

from bench.readers import model_flops_in_window


def read(run):
    f = model_flops_in_window(run)
    if not f or run.peaks is None:
        return None
    return 100.0 * f / run.seconds / run.peaks["bf16_flops"]
