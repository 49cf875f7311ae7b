"""Fixed-point serving: ``ServeEngine`` over the registered
``fixedpoint_solve`` workload (``d_iteration``), whose residual
termination is agreed by MRD across the configuration's ``dp`` replicas,
under an open loop of Poisson arrivals on the host clock.

Each request is a personalization vector as topic-sensitive PageRank
sends it: mass spread evenly over a seed set of ``seed_set_share * n``
vertices drawn from the seed, summing to 1 (the scale of the workload's
default payload).  The graph is the configuration's (``graph_seed``): the
program compiles its operator in as a constant, so a graph per seed would
compile, and write a 1 GiB cache entry, in every run.  After the window,
every certified solution is checked against the float64 reference operator
rebuilt from the configuration: its true residual must be under the
configuration's ``eps``.  A request that is not certified by the end of the
drain counts as failed.
"""

from __future__ import annotations

import contextlib

import numpy as np

from bench import loadgen, serve_loop
from bench import trace as trace_lib
from bench.kinds import (
    RunData,
    checks_entry,
    chip_peaks,
    free_device_memory,
    memory_peak_bytes,
)
from bench.reference.pagerank import PageRank


def payload_bank(n: int, count: int, seed: int, share: float) -> np.ndarray:
    """``count`` personalization vectors, each uniform over its own seed set
    of ``round(share * n)`` vertices (at least one)."""
    rng = loadgen.rng_for(seed, 5)
    k = max(1, int(round(share * n)))
    bank = np.zeros((count, n), np.float32)
    for row in bank:
        row[rng.choice(n, size=k, replace=False)] = 1.0 / k
    return bank


def precision(c: dict):
    import jax

    p = c.get("matmul_precision")
    return jax.default_matmul_precision(p) if p else contextlib.nullcontext()


def build(c: dict, seed: int):
    from repro.serving import make_workload
    from repro.serving.engine import ServeConfig

    wl = make_workload(
        "fixedpoint_solve", solver=c["solver"], n=c["n"], slots=c["slots"],
        dp=c["dp"], damping=c["damping"], out_degree=c["out_degree"],
        seed=c["graph_seed"],
    )
    scfg = ServeConfig(termination=c["termination"], dp=c["dp"], eps=c["eps"])
    return wl, scfg


def run(cell, seed: int, seconds: float, trace: bool, *, devices, since_start):
    import jax

    from repro.serving.engine import Request, ServeEngine

    c, t = cell.config, cell.traffic
    bank = payload_bank(c["n"], t["payloads"], seed, t["seed_set_share"])
    with precision(c):
        wl, scfg = build(c, seed)
        warm = ServeEngine(wl, scfg)
        warm.run([Request(id=-1, payload=bank[0], max_new=t["max_iters"])])
        jax.block_until_ready(wl.wstate)
        wl.reset()
        engine = ServeEngine(wl, scfg)
        del warm

        sched = loadgen.OpenLoop(t["rate_per_s"], seconds, seed)

        def make_request(i):
            return Request(id=i, payload=bank[i % len(bank)], max_new=t["max_iters"])

        tracing = serve_loop.Tracing(*[f * seconds for f in t["trace_window"]]) if trace else None
        setup_s = since_start()
        recs, t0, t_end, t_drained = serve_loop.drive(
            engine, sched, make_request, seconds, needs_done=True,
            drain_s=t["drain_s"], tracing=tracing,
        )
    peak = memory_peak_bytes(devices)
    summary = (
        trace_lib.reduce(tracing.trace)
        if tracing is not None and tracing.trace else None
    )
    del engine, wl
    free_device_memory()

    ref = PageRank(c["n"], damping=c["damping"], out_degree=c["out_degree"],
                   seed=c["graph_seed"])
    sent = [r for r in recs.values() if r.due <= t_end]
    unfinished = sum(
        r.result is None or not r.result.converged for r in sent
    )
    worst, wrong = 0.0, 0
    for r in sent:
        if r.result is not None and r.result.converged:
            res = ref.residual(r.result.output, r.req.payload)
            worst = max(worst, res)
            wrong += res >= c["eps"]
    checks = {
        "true_residual_max": checks_entry(worst, c["eps"]),
        "uncertified_requests": checks_entry(unfinished, 0),
    }
    correct = unfinished == 0 and worst < c["eps"] and len(sent) > 0
    return RunData(
        config=c, traffic=t, seconds=seconds, setup_s=setup_s, t0=t0,
        t_end=t_end, t_drained=t_drained, recs=recs, memory_peak_bytes=peak,
        correct=bool(correct), attempted=len(sent), failed=unfinished + wrong,
        checks=checks, tracing=tracing, trace_summary=summary,
        lag_s=list(sched.lag_s), peaks=chip_peaks(devices),
    )
