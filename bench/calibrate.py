#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, in this one process: the cell's run (a window of
``--seconds``), its compared numbers, and the same numbers for the
lower-precision control put in the program's place on the same inputs:

- LLM cells: the control is the reference computed with float8 (e4m3)
  matmul operands; its reading is the widest gap of the token it puts first
  at each position of the served requests.
- Fixed-point cells: the control is the reference iteration run in
  bfloat16; its reading is the worst float64 true residual of its answers
  to the first requests of the run.  With ``--default-precision 1`` the
  program itself runs its float32 operator at JAX's default matmul
  precision (one bfloat16 pass on a TPU): its own lower-precision path.

One JSON line per seed on stdout.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402


from bench import run as run_lib  # noqa: E402
from bench import spec as spec_lib  # noqa: E402


def control_reading(cell, run, seed: int, *, requests: int = 16):
    kind = cell.config["kind"]
    if kind == "llm_paged":
        from bench.kinds import llm_paged
        from bench.reference import dense

        shape = llm_paged.pool_shape(cell.config, cell.traffic)
        gap, n, _ = llm_paged._widest_gap(
            cell.config, cell.traffic, seed, run.recs, shape, rnd=dense.fp8
        )
        return {"control_logit_gap_max": gap, "control_tokens": n}
    if kind == "fixedpoint":
        import ml_dtypes

        from bench.reference.pagerank import PageRank

        c = cell.config
        ref = PageRank(c["n"], damping=c["damping"], out_degree=c["out_degree"],
                       seed=c["graph_seed"])
        worst = 0.0
        recs = sorted(run.recs.values(), key=lambda r: r.index)[:requests]
        for r in recs:
            x = ref.solve_rounded(r.req.payload, c["eps"],
                                  cell.traffic["max_iters"], ml_dtypes.bfloat16)
            worst = max(worst, ref.residual(x, r.req.payload))
        return {"control_true_residual_max": worst, "control_requests": len(recs)}
    raise ValueError(kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--default-precision", type=int, default=0)
    args = ap.parse_args(argv)
    cell = spec_lib.load_cell(args.workload)
    if args.default_precision:
        cell.config.pop("matmul_precision", None)
    devices = run_lib.check_devices(cell.chips)
    if devices is None:
        return 2
    run_lib.enable_compilation_cache(spec_lib.ROOT)
    kind = importlib.import_module(f"bench.kinds.{cell.config['kind']}")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        run = kind.run(cell, seed, args.seconds, False, devices=devices,
                         since_start=lambda: 0.0)
        out = {"seed": seed, "correct": run.correct, "attempted": run.attempted,
               "failed": run.failed,
               **{k: v["value"] for k, v in run.checks.items()}}
        if args.control:
            out.update(control_reading(cell, run, seed))
        out["wall_s"] = time.perf_counter() - t
        print(json.dumps(out, default=float), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
