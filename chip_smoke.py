#!/usr/bin/env python3
"""Start-up check of the main paths on a TPU, at full llama3.2-1b width.

    python chip_smoke.py            # one chip: the three serving phases
    python chip_smoke.py --chips 4  # four chips: MRD grad-sync training only

Every phase runs in this one process, through the library and CLI entry
points a user calls (``repro.launch.serve.main``, ``repro.launch.train.main``),
with weights, prompts and operators generated from ``--seed``.

One chip:
  1. continuous serving of llama3.2-1b over the block-paged cache (gather
     attention, ``eos_maxlen``): every request retires, none at capacity;
  2. the same prompts through the Pallas paged-attention kernel: the
     compiled decode step holds a TPU kernel, its first-step logits match
     the gather path within ``LOGIT_TOL`` of their scale, and both are
     finite;
  3. the paper's workload: fixed-point serving (D-iteration, residual
     termination agreed over dp=3 replicas) on a 1 GiB float32 operator;
     every request is certified and its true residual, recomputed here in
     float64, is below ``eps``.

Four chips: llama3.2-1b training with ``mrd_zero1`` grad sync at dp=4 against
``gspmd`` at dp=4 on the same seed and data (losses agree within the repo's
differential-test tolerance at every step), then ``mrd_zero1`` at dp=3 on
chips 0-2 with the model cut to 8 layers (finite losses).

Any failed check raises, so the script exits non-zero.  Without a TPU it
exits non-zero before running anything.  The last line of stdout is a JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# relative max-abs difference allowed between the Pallas and gather paths'
# first-step logits: both run the model in bf16 and differ only in the
# attention's accumulation order, whose bf16 rounding (2^-8 relative) then
# passes through every later layer
LOGIT_TOL = 5e-2
# per-step loss agreement of mrd_zero1 against gspmd (the tolerance of
# tests/test_train_distributed.py): same data, same init, different
# reduction order of the float32 gradients
LOSS_RTOL = LOSS_ATOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: tuple  # --arch [--smoke]
    slots: int
    requests: int
    prompt_len: int
    gen: int
    block: int
    greedy_steps: int
    fp_n: int
    fp_requests: int
    fp_slots: int
    fp_gen: int
    train_seq: int
    train_steps: int
    dp3_layers: int


FULL = Sizes(
    model=("--arch", "llama3.2-1b"),
    slots=16, requests=24, prompt_len=512, gen=64, block=16, greedy_steps=16,
    # n x n float32 operator: 16384 rounds up to 16386 (divisible by dp=3),
    # 1.0 GiB on the chip
    fp_n=16384, fp_requests=8, fp_slots=4, fp_gen=400,
    # one sequence per chip; at dp=3 ZeRO-1 shards the float32 optimizer
    # state over the pivot's 2 ranks only, which fits one 16 GB chip at 8
    # of the 16 layers
    train_seq=256, train_steps=5, dp3_layers=8,
)
# the CPU rehearsal of the same control flow (tests/test_chip_smoke.py)
SMOKE = Sizes(
    model=("--arch", "llama3.2-1b", "--smoke"),
    slots=4, requests=6, prompt_len=12, gen=8, block=16, greedy_steps=4,
    fp_n=60, fp_requests=4, fp_slots=2, fp_gen=400,
    train_seq=16, train_steps=3, dp3_layers=1,
)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class Counters:
    """Compilations, their seconds, and persistent-cache hits/misses."""

    def __init__(self):
        self.n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                  "cache_misses": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1
            self.n["compile_s"] += duration

    def snapshot(self):
        return dict(self.n)


def _phase(name, counters, fn, *args):
    before, t0 = counters.snapshot(), time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    d = {k: v - before[k] for k, v in counters.snapshot().items()}
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(
        f"# {name}: wall {wall:.3f} s = compile {d['compile_s']:.3f} s "
        f"({d['compiles']} programs) + the rest | persistent cache "
        f"{d['cache_hits']} hits, {d['cache_misses']} misses"
        + (f" | peak device memory {peak / 2**30:.3f} GiB" if peak else ""),
        flush=True,
    )
    return out


# -- one chip ----------------------------------------------------------------


def serve_paged(sz: Sizes, seed: int):
    """Phase 1: continuous serving over the paged cache, gather attention."""
    from repro.launch import serve

    eng, wl, reqs = serve.main([
        *sz.model, "--continuous", "--workload", "llm_decode_paged",
        "--slots", str(sz.slots), "--block-size", str(sz.block),
        "--requests", str(sz.requests), "--prompt-len", str(sz.prompt_len),
        "--gen", str(sz.gen), "--arrival", "poisson:0.5",
        "--seed", str(seed),
    ])
    s = eng.summary()
    res = eng.results
    check(len(res) == sz.requests, f"{len(res)}/{sz.requests} requests retired")
    check(s["forced_at_capacity"] == 0,
          f"forced_at_capacity = {s['forced_at_capacity']}")
    for r in res.values():
        check(1 <= r.n_tokens <= sz.gen + 1, f"request {r.id}: {r.n_tokens} tokens")
        check(bool(np.all((r.output >= 0) & (r.output < wl.cfg.vocab))),
              f"request {r.id}: token id out of vocabulary")
    return wl, reqs


def pallas_vs_gather(sz: Sizes, seed: int, wl, reqs):
    """Phase 2: the Pallas paged kernel against phase 1's gather path."""
    import jax.numpy as jnp

    from repro.distributed import serve as dserve
    from repro.serving import make_workload

    pool = wl.pool
    wl_p = make_workload(
        "llm_decode_paged", cfg=wl.cfg, mesh=wl.mesh, slots=wl.slots,
        max_len=pool.max_len, max_prompt_len=pool.max_prompt_len,
        params=wl.params, block_size=pool.block_size, attn="pallas",
    )
    wl.reset()
    # the same prompts, admitted through the same prefill, into both pools
    for slot, req in enumerate(reqs[: wl.slots]):
        wl.admit(req, slot, 0)
        wl_p.admit(req, slot, 0)
    active = jnp.ones((wl.slots,), bool)

    def first_step(w, attn):
        step, _ = dserve.make_paged_pool_decode_step(
            w.cfg, w.mesh, w.pool.block_size, attn=attn
        )
        st = w.pool.state
        args = (w.params, st["tokens"], st["pages"], st["tables"], st["slot"],
                st["lengths"], active)
        compiled = jax.jit(step).lower(*args).compile()
        return compiled, np.asarray(compiled(*args)[0], np.float32)

    compiled_p, lp = first_step(wl_p, "pallas")
    _, lg = first_step(wl, "gather")
    # (the CPU rehearsal runs the kernel in interpret mode, as pure HLO)
    check(jax.default_backend() != "tpu"
          or "tpu_custom_call" in compiled_p.as_text(),
          "the Pallas decode step holds no TPU kernel (interpret mode?)")
    check(bool(np.isfinite(lg).all()), "gather-path logits are not finite")
    check(bool(np.isfinite(lp).all()), "Pallas-path logits are not finite")
    scale = float(np.abs(lg).max())
    err = float(np.abs(lp - lg).max())
    print(f"# phase 2: first-step logits max|pallas - gather| = {err:.6g}, "
          f"max|gather| = {scale:.6g}, ratio {err / scale:.6g} "
          f"(limit {LOGIT_TOL})")
    check(err <= LOGIT_TOL * scale, f"logit mismatch {err} > {LOGIT_TOL} * {scale}")

    # greedy continuation on both paths from the same admitted state
    jstep_g = jax.jit(wl.pool.device_step)
    jstep_p = jax.jit(wl_p.pool.device_step)
    sg, sp = wl.pool.state, wl_p.pool.state
    toks_g, toks_p = [], []
    for _ in range(sz.greedy_steps):
        sg = jstep_g(wl.params, sg, active)
        sp = jstep_p(wl_p.params, sp, active)
        toks_g.append(np.asarray(sg["tokens"]))
        toks_p.append(np.asarray(sp["tokens"]))
    agree = float(np.mean(np.asarray(toks_g) == np.asarray(toks_p)))
    print(f"# phase 2: greedy tokens agreeing over {sz.greedy_steps} steps x "
          f"{wl.slots} slots: {agree:.4f}")


def d_iteration_operator(n: int, *, damping: float = 0.85, out_degree: int = 4,
                         seed: int = 0) -> np.ndarray:
    """``damping * P`` in float64: the operator ``repro.asynchrony.solvers``
    generates for ``d_iteration`` (same seeded successor draws), rebuilt
    here as an independent reference."""
    rng = np.random.default_rng(seed)
    T = np.zeros((n, n), np.float64)
    for j in range(n):
        succ = set(rng.choice(n, size=min(out_degree, n), replace=False).tolist())
        succ.add((j + 1) % n)
        succ.discard(j)
        T[list(succ), j] = damping / len(succ)
    return T


def fixedpoint(sz: Sizes, seed: int):
    """Phase 3: certified fixed-point serving, checked in float64."""
    from repro.launch import serve

    eps, damping = 1e-6, 0.85
    eng, wl, reqs = serve.main([
        "--continuous", "--workload", "fixedpoint_solve",
        "--solver", "d_iteration", "--termination", "residual_interval",
        "--n", str(sz.fp_n), "--dp", "3", "--eps", str(eps),
        "--slots", str(sz.fp_slots), "--requests", str(sz.fp_requests),
        "--gen", str(sz.fp_gen), "--seed", str(seed),
    ])
    res = eng.results
    check(len(res) == sz.fp_requests, f"{len(res)}/{sz.fp_requests} solves retired")
    n = wl.pool.n
    T = d_iteration_operator(n, damping=damping)
    worst = 0.0
    for req in reqs:
        r = res[req.id]
        check(r.converged, f"request {r.id} was not certified")
        x = np.asarray(r.output, np.float64)
        v = np.asarray(req.payload, np.float64)
        true_res = float(np.max(np.abs(T @ x + (1.0 - damping) * v - x)))
        check(true_res < eps, f"request {r.id}: true residual {true_res} >= {eps}")
        worst = max(worst, true_res)
    print(f"# phase 3: n={n}, {len(res)} requests certified, worst float64 "
          f"true residual {worst:.6g} < eps {eps}")


# -- four chips ---------------------------------------------------------------


def train_mrd(sz: Sizes, seed: int):
    """MRD grad sync at dp=4 against gspmd, then mrd_zero1 at dp=3."""
    from repro.launch import train

    def run(dp, mode, *extra):
        return np.asarray(train.main([
            *sz.model, "--steps", str(sz.train_steps), "--batch", str(dp),
            "--seq", str(sz.train_seq), "--remat", "full", "--dp", str(dp),
            "--grad-sync", mode, "--seed", str(seed), "--log-every", "1",
            *extra,
        ]))

    l_gspmd = run(4, "gspmd")
    l_mrd = run(4, "mrd_zero1")
    print(f"# train dp=4: gspmd {l_gspmd.tolist()}")
    print(f"# train dp=4: mrd_zero1 {l_mrd.tolist()}")
    print(f"# train dp=4: max|mrd_zero1 - gspmd| = "
          f"{float(np.abs(l_mrd - l_gspmd).max()):.6g}")
    check(bool(np.allclose(l_mrd, l_gspmd, rtol=LOSS_RTOL, atol=LOSS_ATOL)),
          "mrd_zero1 losses do not match gspmd at dp=4")
    l3 = run(3, "mrd_zero1", "--layers", str(sz.dp3_layers))
    print(f"# train dp=3 ({sz.dp3_layers} layers): mrd_zero1 {l3.tolist()}")
    check(bool(np.isfinite(l3).all()), "mrd_zero1 losses at dp=3 are not finite")


def run(sz: Sizes, seed: int, chips: int) -> None:
    counters = Counters()
    if chips == 4:
        _phase("train (4 chips)", counters, train_mrd, sz, seed)
        return
    wl, reqs = _phase("phase 1 serve llm_decode_paged", counters,
                      serve_paged, sz, seed)
    _phase("phase 2 pallas paged attention", counters,
           pallas_vs_gather, sz, seed, wl, reqs)
    del wl, reqs
    _phase("phase 3 fixedpoint_solve", counters, fixedpoint, sz, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compilation_cache

    print(f"# device: {devices[0].device_kind} x {len(devices)}; compile cache "
          f"{enable_compilation_cache()}", flush=True)
    run(FULL, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
