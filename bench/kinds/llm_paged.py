"""Paged LLM serving: ``ServeEngine`` over the registered
``llm_decode_paged`` workload, with its default attention path and the
default ``ServeConfig``, under a closed loop of clients.

Set-up makes the weights on the device from the seed (the benchmark's own
generator, :func:`bench.reference.dense.make_weights`), builds the pool
from the traffic's lengths and warms up every program the window uses by
serving one request.  After the window, a sample of the finished requests,
drawn from the seed and holding the longest, is run through the float32
reference: the widest gap by which a served token's reference logit lies
below the reference's best at its position is compared with the
configuration's limit.
"""

from __future__ import annotations

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
from bench.reference import dense


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    m = c["model"]
    return ModelConfig(
        name=c["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        tie_embeddings=m["tie_embeddings"], param_dtype=m["dtype"],
        compute_dtype=m["dtype"],
    )


def pool_shape(c: dict, t: dict) -> dict:
    """Slots and cache lengths that the traffic needs: one slot per client,
    room for the longest prompt and the longest output."""
    bs = c["serving"]["block_size"]
    pmax, omax = t["prompt_tokens"]["max"], t["output_tokens"]["max"]
    return {
        "slots": t["clients"],
        "max_prompt_len": pmax,
        "max_len": -(-(pmax + omax + 1) // bs) * bs,
        "block_size": bs,
    }


def requests(c: dict, t: dict, seed: int):
    """The request set: every group of ``t["group"]`` requests holds the
    mid-quantiles of the traffic's length distributions, in one shuffled
    order that is the same for every seed; token ids drawn from the seed.
    A window holds some 45 requests of a closed loop, and their order sets
    who queues behind whom: orders drawn per seed moved the tails by a
    quarter between seeds, against 0.1% between two runs of one seed."""
    n, g = t["requests"], t["group"]
    order = loadgen.rng_for(0, 4)
    lens = []
    for key in ("prompt_tokens", "output_tokens"):
        q = loadgen.quantile_lengths(t[key], g)
        lens.append(np.concatenate([order.permutation(q) for _ in range(-(-n // g))])[:n])
    rng = loadgen.rng_for(seed, 2)
    ids = c["model"]["token_ids_below"]
    prompts = [rng.integers(0, ids, size=int(p)).astype(np.int32) for p in lens[0]]
    return prompts, [int(o) for o in lens[1]]


def _check_layout(cfg, params):
    """The weights must have the program's parameter layout."""
    import jax

    from repro.models import transformer

    want = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights do not match the program's layout:\n{got}\n!=\n{want}")


def run(cell, seed: int, seconds: float, trace: bool, *, devices, since_start):
    import jax

    from repro.launch.train import build_mesh
    from repro.serving import make_workload
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    c, t = cell.config, cell.traffic
    m = c["model"]
    cfg = model_config(c)
    shape = pool_shape(c, t)
    prompts, outs = requests(c, t, seed)

    params = dense.make_weights(m, seed)
    _check_layout(cfg, params)
    mesh = build_mesh(1, 1)
    wl = make_workload(
        c["serving"]["workload"], cfg=cfg, mesh=mesh, params=params,
        slots=shape["slots"], max_len=shape["max_len"],
        max_prompt_len=shape["max_prompt_len"], block_size=shape["block_size"],
    )
    # warm-up: one request compiles the admission, the fused tick and the
    # read-backs (slot indices are operands, so one of each)
    warm = ServeEngine(wl, ServeConfig())
    warm.run([Request(id=-1, prompt=prompts[0], max_new=3, eos=-1)])
    jax.block_until_ready(wl.wstate)
    wl.reset()
    engine = ServeEngine(wl, ServeConfig())
    del warm

    sched = loadgen.ClosedLoop(t["clients"], t["ramp_s"], len(prompts))

    def make_request(i):
        return Request(id=i, prompt=prompts[i], max_new=outs[i], eos=-1)

    tracing = (
        serve_loop.Tracing(*[f * seconds for f in t["trace_window"]]) if trace else None
    )
    setup_s = since_start()
    recs, t0, t_end, t_drained = serve_loop.drive(
        engine, sched, make_request, seconds, needs_done=False,
        drain_s=t["drain_s"], tracing=tracing,
    )
    peak = memory_peak_bytes(devices)
    summary = (
        trace_lib.reduce(tracing.trace)
        if tracing is not None and tracing.trace else None
    )

    # free the program's state, then judge the finished requests
    del engine, wl, params
    free_device_memory()
    sent = [r for r in recs.values() if r.due <= t_end]
    no_first = sum(r.first is None for r in sent)
    gap, n_tok, n_req = _widest_gap(c, t, seed, recs, shape)
    limit = c["limits"]["logit_gap"]
    checks = {
        "logit_gap_max": checks_entry(gap, limit),
        "tokens_compared": checks_entry(n_tok, t["check"]["min_tokens"]),
        "requests_without_first_token": checks_entry(no_first, 0),
    }
    correct = (
        gap is not None and gap <= limit and no_first == 0
        and n_tok >= t["check"]["min_tokens"]
    )
    return RunData(
        config=c, traffic=t, seconds=seconds, setup_s=setup_s, t0=t0,
        t_end=t_end, t_drained=t_drained, recs=recs, memory_peak_bytes=peak,
        correct=bool(correct), attempted=len(sent), failed=no_first,
        checks=checks, tracing=tracing, trace_summary=summary,
        peaks=chip_peaks(devices),
    )


def sample(recs, t: dict, seed: int):
    """Finished requests to check: the longest, then others in an order
    drawn from the seed, until ``t["check"]["max_tokens"]`` served tokens."""
    done = sorted(
        (r for r in recs.values() if r.result is not None),
        key=lambda r: (-len(r.result.output), r.index),
    )
    if not done:
        return []
    rest = done[1:]
    order = loadgen.rng_for(seed, 3).permutation(len(rest))
    picked, total = [done[0]], len(done[0].result.output)
    for i in order:
        r = rest[int(i)]
        if total + len(r.result.output) > t["check"]["max_tokens"]:
            continue
        picked.append(r)
        total += len(r.result.output)
    return picked


def _widest_gap(c, t, seed, recs, shape, rnd=None):
    """(widest gap, tokens compared, requests compared) over the sample."""
    picked = sample(recs, t, seed)
    if not picked:
        return None, 0, 0
    w = dense.make_weights(c["model"], seed)
    widest, n = 0.0, 0
    for r in picked:
        gaps = dense.served_gaps(
            c["model"], w, r.req.prompt, r.result.output,
            seq_len=shape["max_len"], rows_len=t["output_tokens"]["max"],
            rnd=rnd,
        )
        widest = max(widest, float(gaps.max()))
        n += int(gaps.shape[0])
    del w
    return widest, n, len(picked)
