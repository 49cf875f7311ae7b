"""4 n^2 bytes per fused tick (one read of the float32 operator) at the
chip's HBM bandwidth, over the fused tick program's device time per tick."""

from bench import flops
from bench.readers import program_s, traced_ticks


def read(run):
    t = program_s(run, "_fused_loop")
    ticks = traced_ticks(run)
    if not t or not ticks or run.peaks is None:
        return None
    nbytes = flops.operator_bytes(run.config["n"]) * ticks
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
