"""Train-step benchmarks: ready-bucket grad-sync overlap (DESIGN.md S16)
and async device->host checkpointing.

JSON: writes BENCH_train.json ({"measured": [...], "meta": {...}}).
CSV on stdout: name,us_per_call[,ratio]

Three row families:

- ``train_step_{mode}_{variant}_jit_dp{dp}``: the real jitted train step
  on a multi-device CPU mesh, overlap vs no-overlap.  Inside one fused
  XLA computation the CPU backend schedules ops itself, so the two
  variants are expected to land at *parity* — these rows gate the
  bit-identical-loss contract and act as a regression tripwire
  (overlap must not be slower than baseline beyond JIT_NOISE_FLOOR).

- ``gradsync_{mode}_{variant}_dispatch_p{p}``: the dispatch regime —
  host-driven op-by-op execution where bucket *issue order* is
  observable.  A single-core CPU host has no async interconnect, so the
  wire is modeled: every stage of the real
  :class:`repro.collectives.plans.BucketPipeline` additionally occupies
  a discrete-event NIC for its alpha-beta time (the same LinkModel
  framing as BENCH_mrd's model rows), while the *real* jitted backward
  segments (the same 3-segment VJP split as ``gradsync/overlap.py``)
  burn wall-clock.  A pump thread advances in-flight buckets as their
  modeled transfers land, so wire time genuinely elapses concurrently
  with segment compute.  Baseline admits every bucket after the full
  backward; overlap admits each readiness group as its segment
  finishes — the measured delta is the comm hidden under compute, the
  latency-hiding the paper's non-blocking reduction targets.  Stage
  math, packing, and admission policy are the real engine; both
  variants run identical compute and identical stage ops, and the
  reduced buffers must be bit-identical across admission orders.

- ``ckpt_save_*``: Checkpointer.save call time by blocking mode —
  ``block=True`` (full write), ``block='transfer'`` (device->host
  materialize only; the pre-S16 synchronous-snapshot stall), and
  ``block=False`` (async staging; the call must return without waiting
  on the transfer).

``--quick`` shrinks the grid for CI smoke.  ``--check`` asserts:
losses bit-identical overlap vs baseline (jit rows, microbatches=1);
jit overlap <= baseline x JIT_NOISE_FLOOR; dispatch overlap <=
baseline x DISPATCH_GATE (i.e. overlap *reduces* dispatch-regime step
time); reduced buffers bit-identical across admission orders; async
checkpoint save call <= max(0.5 x transfer stall, CKPT_FLOOR_US).
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding

from repro.checkpoint.checkpointer import Checkpointer
from repro.collectives import buckets, plans
from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import step as step_lib
from repro.distributed.gradsync import overlap as overlap_lib
from repro.models import transformer
from repro.models.layers import dtype_of
from repro.optim.optimizer import OptimizerConfig

# Inside one jitted step XLA:CPU schedules both variants itself, so overlap
# is parity-by-construction there; the floor only absorbs walltime noise.
JIT_NOISE_FLOOR = 1.30
# Dispatch regime: overlap must actually reduce step time.
DISPATCH_GATE = 0.95
# Fraction of measured segment-compute time the modeled wire is calibrated
# to (comm-bound-ish, the regime where overlap matters).
COMM_RATIO = 0.8
ALPHA_S = 50e-6  # per-stage dispatch/launch latency of the modeled NIC
CKPT_FLOOR_US = 2000.0


# ---------------------------------------------------------------------------
# jit regime: the real train step, overlap vs baseline
# ---------------------------------------------------------------------------


def _jit_step_run(mode: str, dp: int, overlap: bool, steps: int, reps: int):
    """Best-of-``reps`` us/step of the real jitted train step, plus the
    per-step losses (for the bitwise gate)."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = jax.make_mesh(
        (dp,), ("data",), axis_types=(AxisType.Auto,),
        devices=jax.devices()[:dp],
    )
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync=mode, monitor=True,
        bucket_bytes=1 << 15, overlap=overlap,
        optimizer=OptimizerConfig(lr=1e-3, schedule="const", warmup_steps=0),
    )
    train_step, init_state, state_specs, _ = step_lib.make_train_step(cfg, mesh, tcfg)
    with mesh:
        state0 = init_state(jax.random.PRNGKey(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs(state0))
        state0 = jax.device_put(state0, sh)
        jstep = jax.jit(train_step)
        warm = SyntheticPipeline(cfg, DataConfig(batch=2 * dp, seq_len=32, seed=0), mesh)
        jax.block_until_ready(jstep(state0, warm.next_batch())[0])  # compile
        best, losses = float("inf"), []
        for _ in range(reps):
            # every rep replays the same trajectory, so the loss list is
            # deterministic and the timing work identical across reps
            pipe = SyntheticPipeline(
                cfg, DataConfig(batch=2 * dp, seq_len=32, seed=1), mesh
            )
            state, rl = state0, []
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = jstep(state, pipe.next_batch())
                rl.append(m["loss"])
            jax.block_until_ready(state)
            best = min(best, (time.perf_counter() - t0) / steps)
            losses = [float(v) for v in rl]
        return best * 1e6, losses


def jit_rows(modes, quick: bool):
    dp = 4
    steps, reps = (3, 2) if quick else (5, 3)
    out = []
    for mode in modes:
        t_base, l_base = _jit_step_run(mode, dp, False, steps, reps)
        t_ovl, l_ovl = _jit_step_run(mode, dp, True, steps, reps)
        bitwise = l_base == l_ovl
        for variant, us in (("baseline", t_base), ("overlap", t_ovl)):
            row = {
                "name": f"train_step_{mode}_{variant}_jit_dp{dp}",
                "mode": mode, "regime": "jit", "dp": dp,
                "us_per_call": round(us, 1),
            }
            if variant == "overlap":
                row["ratio_vs_baseline"] = round(t_ovl / t_base, 3)
                row["losses_bitwise"] = bitwise
            out.append(row)
    return out


# ---------------------------------------------------------------------------
# dispatch regime: real BucketPipeline + segment VJPs over a modeled NIC
# ---------------------------------------------------------------------------


class _LinkSim:
    """Discrete-event model of one rank's NIC: transfers serialize on the
    link; each costs ``alpha + bytes*beta`` (the repo's alpha-beta model)."""

    def __init__(self, alpha_s: float, beta_s_per_byte: float):
        self.alpha = alpha_s
        self.beta = beta_s_per_byte
        self.free_at = 0.0

    def occupy(self, nbytes: float, now: float) -> float:
        start = max(now, self.free_at)
        self.free_at = start + self.alpha + nbytes * self.beta
        return self.free_at


class _DispatchRun:
    """One timed reduction: real per-bucket BucketPipelines advanced by a
    pump thread as their modeled stage transfers land."""

    def __init__(self, plan, layout, elt_bytes: int, link: _LinkSim):
        self.plan = plan
        self.layout = layout
        self.elt_bytes = elt_bytes
        self.link = link
        self.fractions = [
            st.payload_fraction for st, _, _, _ in plan.bound_stage_table()
        ]
        self.n_stages = len(self.fractions)
        self.lock = threading.Lock()
        self.pipes: dict = {}
        self.rem: dict = {}       # stages left to finish per in-flight bucket
        self.ready_at: dict = {}  # modeled arrival of the in-flight stage
        self.done: dict = {}
        self.all_done = threading.Event()
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def _stage_bytes(self, bi: int, si: int) -> float:
        return self.layout.buckets[bi].length * self.elt_bytes * self.fractions[si]

    def admit(self, bi: int, buf) -> None:
        with self.lock:
            pipe = self.plan.pipeline()
            pipe.admit(bi, buf)  # issues stage 0
            if self.n_stages == 0:
                self.done[bi] = pipe.drain()[bi]
            else:
                self.pipes[bi] = pipe
                self.rem[bi] = self.n_stages
                self.ready_at[bi] = self.link.occupy(
                    self._stage_bytes(bi, 0), self.now()
                )
            if len(self.done) == len(self.layout.buckets):
                self.all_done.set()

    def pump(self):
        """Advance every bucket whose modeled transfer has arrived; returns
        the next deadline (or None if nothing is in flight)."""
        with self.lock:
            progressed = True
            while progressed:
                progressed = False
                now = self.now()
                for bi in list(self.pipes):
                    if now < self.ready_at[bi]:
                        continue
                    pipe = self.pipes[bi]
                    pipe.advance()  # finish the arrived stage, issue the next
                    self.rem[bi] -= 1
                    if self.rem[bi] == 0:
                        self.done[bi] = pipe.drain()[bi]
                        del self.pipes[bi], self.rem[bi], self.ready_at[bi]
                    else:
                        si = self.n_stages - self.rem[bi]
                        self.ready_at[bi] = self.link.occupy(
                            self._stage_bytes(bi, si), self.now()
                        )
                    progressed = True
            if len(self.done) == len(self.layout.buckets):
                self.all_done.set()
            return min(self.ready_at.values(), default=None)


def _pump_loop(run: _DispatchRun, stop: threading.Event):
    while not (stop.is_set() or run.all_done.is_set()):
        nxt = run.pump()
        if nxt is None:
            time.sleep(1e-4)  # nothing admitted yet
        else:
            dt = nxt - run.now()
            if dt > 0:
                time.sleep(min(dt, 1e-3))


def _dispatch_ctx(p: int):
    """The model + jitted segment functions for the dispatch rows: the
    same 3-segment VJP split as gradsync/overlap.py, untied so the output
    head is a real early-readiness gradient group."""
    cfg = registry.override(
        registry.get_smoke_config("llama3.2-1b"),
        tie_embeddings=False, vocab=4096, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=256, n_layers=4,
    )
    cdt = dtype_of(cfg.compute_dtype)
    fp32 = lambda t: jax.tree.map(lambda g: g.astype(jnp.float32), t)

    def embed_fn(pe, batch):
        x, _ = transformer._embed_inputs(pe, batch, cfg)
        return x.astype(cdt)

    @jax.jit
    def fwd(params, batch):
        _, ps, pe = overlap_lib._split_params(params)
        x0 = embed_fn(pe, batch)
        positions = jnp.arange(x0.shape[1])[None, :]
        x1, aux = transformer._run_stack(ps, x0, cfg, positions, None)
        return x0, x1, aux

    @jax.jit
    def head_bwd(params, x1, aux, batch):
        ph, _, _ = overlap_lib._split_params(params)

        def f(ph_, x, a):
            return transformer._train_head(ph_, x, a, batch, cfg, 0)

        loss, vjp, _metrics = jax.vjp(f, ph, x1, aux, has_aux=True)
        gh, ct_x1, ct_aux = vjp(jnp.ones_like(loss))
        return loss, fp32(gh), ct_x1, ct_aux

    @jax.jit
    def stack_bwd(params, x0, ct_x1, ct_aux):
        _, ps, _ = overlap_lib._split_params(params)
        positions = jnp.arange(x0.shape[1])[None, :]
        (_x1, _aux), vjp = jax.vjp(
            lambda ps_, x: transformer._run_stack(ps_, x, cfg, positions, None),
            ps, x0,
        )
        gs, ct_x0 = vjp((ct_x1, ct_aux))
        return fp32(gs), ct_x0

    @jax.jit
    def embed_bwd(params, ct_x0, batch):
        _, _, pe = overlap_lib._split_params(params)
        (ge,) = jax.vjp(lambda pe_: embed_fn(pe_, batch), pe)[1](ct_x0)
        return fp32(ge)

    @jax.jit
    def finish(red):
        # stands in for the (admission-order-independent) optimizer tail
        return sum(jnp.sum(r.astype(jnp.float32) ** 2) for r in red)

    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = SyntheticPipeline(
        cfg, DataConfig(batch=8, seq_len=128, seed=0)
    ).next_batch()
    pshape = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    return {
        "cfg": cfg, "params": params, "batch": batch, "pshape": pshape,
        "fwd": fwd, "head_bwd": head_bwd, "stack_bwd": stack_bwd,
        "embed_bwd": embed_bwd, "finish": finish, "p": p,
    }


_DISPATCH_PLANS = {
    # the plan each gradsync mode drives at gradient scale, on the sim
    # executor (stacked [p, n] buffers)
    "mrd_zero1": lambda p: plans.reduce_scatter_plan(p=p, op="sum", executor="sim"),
    "compressed": lambda p: plans.reduce_scatter_plan(
        p=p, op="sum", transform="int8", executor="sim"
    ),
    "mrd_paper": lambda p: plans.allreduce_plan(
        schedule="mrd", p=p, op="sum", executor="sim"
    ),
    "mrd_leaf": lambda p: plans.allreduce_plan(
        schedule="mrd", p=p, op="sum", executor="sim"
    ),
}
_WIRE_ELT_BYTES = {"mrd_zero1": 4, "compressed": 1, "mrd_paper": 4, "mrd_leaf": 4}


def _dispatch_once(ctx, plan, layout, koffs, bgroups, elt_bytes, link, overlap: bool):
    """One timed step: segments + reduction.  Returns (seconds, loss, red)."""
    p = ctx["p"]
    params, batch = ctx["params"], ctx["batch"]
    leaves: list = [None] * layout.n_leaves

    def scatter(piece):
        for k in sorted(piece.keys()):
            base = koffs[k]
            for j, leaf in enumerate(jax.tree.leaves(piece[k])):
                leaves[base + j] = jnp.broadcast_to(leaf[None], (p,) + leaf.shape)

    def admit_group(run, gi):
        for bi, bg in enumerate(bgroups):
            if bg == gi:
                run.admit(bi, buckets.pack_bucket(leaves, layout, bi))

    run = _DispatchRun(plan, layout, elt_bytes, link)
    stop = threading.Event()
    th = threading.Thread(target=_pump_loop, args=(run, stop), daemon=True)
    th.start()
    t0 = time.perf_counter()
    x0, x1, aux = jax.block_until_ready(ctx["fwd"](params, batch))
    loss, gh, ct_x1, ct_aux = jax.block_until_ready(
        ctx["head_bwd"](params, x1, aux, batch)
    )
    scatter(gh)
    if overlap:
        admit_group(run, 0)
    gs, ct_x0 = jax.block_until_ready(ctx["stack_bwd"](params, x0, ct_x1, ct_aux))
    scatter(gs)
    if overlap:
        admit_group(run, 1)
    ge = jax.block_until_ready(ctx["embed_bwd"](params, ct_x0, batch))
    scatter(ge)
    if overlap:
        admit_group(run, 2)
    else:
        for gi in range(overlap_lib.N_GROUPS):
            admit_group(run, gi)
    run.all_done.wait()
    stop.set()
    th.join()
    red = [run.done[i] for i in range(len(layout.buckets))]
    jax.block_until_ready(ctx["finish"](red))
    dt = time.perf_counter() - t0
    return dt, float(loss), red


def dispatch_rows(modes, quick: bool):
    p = 8
    reps = 2 if quick else 3
    ctx = _dispatch_ctx(p)
    pshape = ctx["pshape"]
    koffs = overlap_lib.key_offsets(pshape)
    lgroups = overlap_lib.leaf_groups(pshape)

    # calibrate the modeled wire against measured segment compute: one
    # compute-only pass (after compiling) gives C; beta is set so each
    # mode's total wire time is COMM_RATIO x C
    def compute_only():
        t0 = time.perf_counter()
        x0, x1, aux = jax.block_until_ready(ctx["fwd"](ctx["params"], ctx["batch"]))
        _, _, ct_x1, ct_aux = jax.block_until_ready(
            ctx["head_bwd"](ctx["params"], x1, aux, ctx["batch"])
        )
        _, ct_x0 = jax.block_until_ready(
            ctx["stack_bwd"](ctx["params"], x0, ct_x1, ct_aux)
        )
        jax.block_until_ready(ctx["embed_bwd"](ctx["params"], ct_x0, ctx["batch"]))
        return time.perf_counter() - t0

    compute_only()  # compile
    c_seconds = min(compute_only() for _ in range(3))

    out = []
    for mode in modes:
        plan = _DISPATCH_PLANS[mode](p)
        elt_bytes = _WIRE_ELT_BYTES[mode]
        fp32_stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((p,) + s.shape, jnp.float32), pshape
        )
        layout = buckets.build_layout(
            fp32_stacked, bucket_bytes=1 << 20,
            quantum=plan.pad_quantum(), stacked=p,
        )
        bgroups = overlap_lib.bucket_groups(layout, lgroups)
        fractions = [
            st.payload_fraction for st, _, _, _ in plan.bound_stage_table()
        ]
        total_bytes = sum(
            b.length * elt_bytes * f for b in layout.buckets for f in fractions
        )
        beta = COMM_RATIO * c_seconds / total_bytes
        times, reds = {}, {}
        for variant, overlap in (("baseline", False), ("overlap", True)):
            best = float("inf")
            for rep in range(reps + 1):  # rep 0 warms the jit caches
                link = _LinkSim(ALPHA_S, beta)
                dt, _loss, red = _dispatch_once(
                    ctx, plan, layout, koffs, bgroups, elt_bytes, link, overlap
                )
                if rep > 0:
                    best = min(best, dt)
            times[variant], reds[variant] = best, red
        bitwise = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(reds["baseline"], reds["overlap"])
        )
        for variant in ("baseline", "overlap"):
            row = {
                "name": f"gradsync_{mode}_{variant}_dispatch_p{p}",
                "mode": mode, "regime": "dispatch", "p": p,
                "n_buckets": len(layout.buckets),
                "us_per_call": round(times[variant] * 1e6, 1),
                "beta_s_per_byte": beta,
            }
            if variant == "overlap":
                row["ratio_vs_baseline"] = round(
                    times["overlap"] / times["baseline"], 3
                )
                row["reduced_bitwise"] = bitwise
            out.append(row)
    return out


# ---------------------------------------------------------------------------
# checkpoint stall rows
# ---------------------------------------------------------------------------


def ckpt_rows(quick: bool):
    rng = np.random.default_rng(0)
    n = 32_768 if quick else 262_144  # x32 leaves: 4MB quick, 32MB full
    state = {
        "params": {
            f"w{i:02d}": jnp.asarray(rng.standard_normal(n), jnp.float32)
            for i in range(32)
        },
        "step": jnp.zeros((), jnp.int32),
    }
    jax.block_until_ready(state)
    out, step = [], 0
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        for name, block in (
            ("ckpt_save_blocking", True),
            ("ckpt_save_transfer_stall", "transfer"),
            ("ckpt_save_async_call", False),
        ):
            best = float("inf")
            for _ in range(3):
                ck.wait()  # the timed call must not pay the previous write
                step += 1
                t0 = time.perf_counter()
                ck.save(step, state, block=block)
                best = min(best, time.perf_counter() - t0)
            ck.wait()
            out.append({
                "name": name, "regime": "ckpt",
                "block": str(block),
                "state_mb": round(n * 32 * 4 / 2**20, 1),
                "us_per_call": round(best * 1e6, 1),
            })
    return out


# ---------------------------------------------------------------------------


def main(json_path: str = "BENCH_train.json", quick: bool = False, check: bool = False):
    jit_modes = ["mrd_zero1", "compressed"] if quick else [
        "mrd_zero1", "compressed", "mrd_paper", "mrd_leaf"
    ]
    disp_modes = ["mrd_zero1", "compressed"] if quick else list(_DISPATCH_PLANS)

    measured = (
        jit_rows(jit_modes, quick)
        + dispatch_rows(disp_modes, quick)
        + ckpt_rows(quick)
    )
    for r in measured:
        print(f"{r['name']},{r['us_per_call']},{r.get('ratio_vs_baseline', '')}")

    meta = {
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "jit_noise_floor": JIT_NOISE_FLOOR,
        "dispatch_gate": DISPATCH_GATE,
        "dispatch_gate_modes": ["mrd_zero1", "compressed"],
        "comm_ratio": COMM_RATIO,
        "alpha_s": ALPHA_S,
        "notes": [
            "jit rows: real jitted train step; XLA:CPU schedules the fused "
            "program itself, so overlap==baseline at parity is the expected "
            "result — the rows gate bit-identical losses and regressions.",
            "dispatch rows: host-driven op-by-op regime; wire time is the "
            "alpha-beta LinkModel as a discrete-event NIC (calibrated to "
            "comm_ratio x measured segment compute) because a single-core "
            "CPU host has no async interconnect; stage math, packing, and "
            "admission policy are the real BucketPipeline engine.",
            "ckpt rows: Checkpointer.save call time by blocking mode on a "
            "synthetic state; block=False must not wait on device->host.",
        ],
    }
    with open(json_path, "w") as f:
        json.dump({"measured": measured, "meta": meta}, f, indent=2)
    print(f"# wrote {json_path}")

    if check:
        by_name = {r["name"]: r for r in measured}
        for r in measured:
            if r.get("regime") == "jit" and "ratio_vs_baseline" in r:
                assert r["losses_bitwise"], (
                    f"{r['name']}: overlap losses differ bitwise from baseline"
                )
                assert r["ratio_vs_baseline"] <= JIT_NOISE_FLOOR, (
                    f"{r['name']}: jit overlap regressed "
                    f"{r['ratio_vs_baseline']}x > {JIT_NOISE_FLOOR}x floor"
                )
            if r.get("regime") == "dispatch" and "ratio_vs_baseline" in r:
                assert r["reduced_bitwise"], (
                    f"{r['name']}: reduced buffers differ across admission orders"
                )
                # Hard speedup gate on the acceptance modes; the AR modes
                # (mrd_paper/mrd_leaf) run log2(p) full-payload butterfly
                # stages per bucket, so the last bucket's wire time dominates
                # both variants and the overlap win is smaller — gate those
                # at no-regression only.
                gated = any(m in r["name"] for m in ("mrd_zero1", "compressed"))
                gate = DISPATCH_GATE if gated else JIT_NOISE_FLOOR
                assert r["ratio_vs_baseline"] <= gate, (
                    f"{r['name']}: overlap dispatch ratio "
                    f"{r['ratio_vs_baseline']}x > {gate}x gate"
                )
        stall = by_name["ckpt_save_transfer_stall"]["us_per_call"]
        async_us = by_name["ckpt_save_async_call"]["us_per_call"]
        assert async_us <= max(0.5 * stall, CKPT_FLOOR_US), (
            f"async save call {async_us}us blocks vs transfer stall {stall}us"
        )
        print("# all checks passed")


def use_host_devices(n: int = 8) -> None:
    """CPU-only entry: split the host into ``n`` XLA devices, since the jit
    rows need a real DP extent.  Takes effect only before JAX creates its
    backend, so callers run it before any device query."""
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()


if __name__ == "__main__":
    use_host_devices()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_train.json", help="output JSON path")
    ap.add_argument("--quick", action="store_true",
                    help="reduced grid (CI smoke): fewer modes/steps/reps")
    ap.add_argument("--check", action="store_true",
                    help="assert the overlap/bitwise/checkpoint gates")
    args = ap.parse_args()
    main(json_path=args.json, quick=args.quick, check=args.check)
