#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate once, to find the highest rate the
system sustains (the knee), from which the cell's fixed rate is set.

    python3 bench/sweep.py --workload pagerank.ppr --rates 50,100,150 --seconds 15

In this one process, for each rate: the cell's run at that rate and seed,
then one JSON line with the rate offered, the rate completed in the window,
the backlog at the window's end (sent minus certified) and the p95 latency.
A rate is sustained when the backlog stays within the slots and the p95
does not run away.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402

from bench import readers  # noqa: E402
from bench import run as run_lib  # noqa: E402
from bench import spec as spec_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec_lib.load_cell(args.workload)
    devices = run_lib.check_devices(cell.chips)
    if devices is None:
        return 2
    run_lib.enable_compilation_cache(spec_lib.ROOT)
    kind = importlib.import_module(f"bench.kinds.{cell.config['kind']}")
    for rate in [float(r) for r in args.rates.split(",")]:
        c = dataclasses.replace(
            cell, traffic={**cell.traffic, "rate_per_s": rate, "drain_s": 5.0})
        run = kind.run(c, args.seed, args.seconds, False, devices=devices,
                         since_start=lambda: 0.0)
        sent = run.in_window()
        done = [r for r in sent if r.done is not None and r.done <= run.t_end]
        print(json.dumps({
            "rate": rate, "sent": len(sent),
            "completed_per_s": len(done) / args.seconds,
            "backlog_at_end": len(sent) - len(done),
            "p95_ms": readers.p95(readers.waits_ms(run, lambda r: r.done)),
            "correct": run.correct,
        }), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
