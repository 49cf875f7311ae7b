"""Benchmark harness: one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows from every bench.  The roofline
table (dry-run derived) is produced by ``benchmarks.roofline_table`` and reads
results/dryrun + results/calibrate.

Each bench runs with the obs subsystem live and leaves a per-run trace
artifact ``TRACE_<bench>.json`` next to its ``BENCH_<bench>.json`` (load in
Perfetto / chrome://tracing) — a bench regression in a BENCH diff comes
with the trace that produced it.  ``--no-trace`` restores bare runs.
"""

from __future__ import annotations

import argparse
import sys


def run_bench(name: str, fn, trace: bool = True) -> None:
    """Run one bench entry point under a fresh telemetry instance and
    write ``TRACE_<name>.json`` at exit.  Fresh per bench: spans from one
    bench never bleed into the next bench's artifact."""
    if not trace:
        fn()
        return
    from repro import obs

    obs.reset()
    obs.configure("null", background=False)
    try:
        fn()
    finally:
        obs.telemetry().registry.flush()
        tr = obs.telemetry().tracer
        path = f"TRACE_{name}.json"
        tr.write_chrome_trace(path, process_name=f"bench_{name}")
        s = tr.summary()
        print(f"# trace: {s['spans']} spans, {s['instants']} instants "
              f"-> {path}", file=sys.stderr)
        obs.shutdown()
        obs.reset()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the per-bench TRACE_<name>.json artifacts")
    args = ap.parse_args(argv)

    from benchmarks import (
        bench_async,
        bench_compression,
        bench_detection,
        bench_mrd,
        bench_train_step,
    )

    bench_train_step.use_host_devices()
    print("name,us_per_call,derived")
    for mod in (bench_mrd, bench_detection, bench_async, bench_compression,
                bench_train_step):
        print(f"# --- {mod.__name__} ---", file=sys.stderr)
        short = mod.__name__.rsplit(".", 1)[-1].removeprefix("bench_")
        run_bench(short, mod.main, trace=not args.no_trace)


if __name__ == "__main__":
    main()
