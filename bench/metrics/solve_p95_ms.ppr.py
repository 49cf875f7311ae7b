"""p95 over the requests due in the window of certified-solution time - due
time; one never certified waits until the end of the drain.  The tail of
all requests: a host stall of ~0.1 s inside the window moves it by a tenth
(PERF.md), so it is read per layer beside the end-to-end median."""

from bench.readers import p95, solve_waits_ms


def read(run):
    return p95(solve_waits_ms(run))
