#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file and
a traffic mix; the configuration's ``kind`` picks the module
(``bench/kinds/<kind>.py``) that builds the program under test from the
seed, warms it up, offers the traffic for ``--seconds`` on the host clock
and checks what the timed path produced against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiled run's per-layer metrics, the device's busy and
window seconds and a breakdown.  The last line of stdout is the result as
one JSON object; the numbers compared for ``correct`` are also the last
lines on stderr.  Without a TPU, or with fewer chips than the cell asks
for, or on a chip whose peaks are not known, it prints no result and exits
with 2.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path


def _process_age_s() -> float:
    """Seconds since this process started (0 where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE0, _T0 = _process_age_s(), time.perf_counter()

_ROOT = Path(__file__).resolve().parents[1]
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402

from bench import spec as spec_lib  # noqa: E402
from bench.peaks import peaks  # noqa: E402


def since_process_start() -> float:
    return _AGE0 + (time.perf_counter() - _T0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int):
    """The devices to run on, or None (with the reason on stderr)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX finds {len(devs)}",
              file=sys.stderr)
        return None
    try:
        peaks(devs[0].device_kind)
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return None
    return devs[:chips]


def enable_compilation_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(args, *, root=spec_lib.ROOT, bench_dir=spec_lib.BENCH,
             devices=None, clock=since_process_start) -> dict:
    """Run the cell and return its result (the module is told the devices;
    tests pass the CPU's)."""
    cell = spec_lib.load_cell(args.workload, root, bench_dir)
    if devices is None:
        if not (root / "src" / "repro").is_dir():
            print(f"bench: no program under test at {root / 'src'}",
                  file=sys.stderr)
            raise SystemExit(2)
        devices = check_devices(cell.chips)
        if devices is None:
            raise SystemExit(2)
        enable_compilation_cache(root)
    kind = importlib.import_module(f"bench.kinds.{cell.config['kind']}")
    run = kind.run(cell, args.seed, args.seconds, bool(args.trace),
                     devices=devices, since_start=clock)
    entries = cell.per_layer if args.trace else cell.end_to_end
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    out = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": spec_lib.read_metrics(entries, run, bench_dir),
        "device": device,
    }
    if args.trace:
        red = run.trace_summary or {}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
        if red.get("breakdown"):
            out["breakdown"] = red["breakdown"]
    out["checks"] = run.checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    out = run_cell(args)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
