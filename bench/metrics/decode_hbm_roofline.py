"""Least bytes of the traced decode ticks (every weight once per tick plus
the live K/V rows each active slot attends to) at the chip's HBM
bandwidth, over the fused tick program's device time."""

from bench import flops
from bench.readers import decode_positions, program_s, traced_ticks, traced_window


def read(run):
    t = program_s(run, "_fused_loop")
    win = traced_window(run)
    ticks = traced_ticks(run)
    if not t or win is None or not ticks or run.peaks is None:
        return None
    m = run.config["model"]
    nbytes = (flops.weight_bytes(m) * ticks
              + flops.kv_bytes_per_token(m) * decode_positions(run, *win))
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
