"""p95 over the requests due in the window of certified-solution time - due
time; one never certified waits until the end of the drain."""

from bench.readers import p95, waits_ms


def read(run):
    return p95(waits_ms(run, lambda r: r.done if r.result is not None
                        and r.result.converged else None))
