"""Serving driver: static batch or continuous batching (``--continuous``).

Static (the historical path, now with honest timing — ``block_until_ready``
fences around the timed regions, prefill and decode reported separately):

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --batch 4 --prompt-len 16 --gen 32

Continuous batching via the ``repro.serving`` subsystem (DESIGN.md S13):

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --continuous --slots 4 --requests 16 --arrival poisson:0.5 \\
      --scheduler fcfs --gen 24

  # block-paged cache with prefix sharing (DESIGN.md S14): same tokens,
  # more concurrent requests per byte of cache
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --continuous --workload llm_decode_paged --slots 8 --block-size 8

  # per-query fixed-point solves (D-iteration / personalized PageRank),
  # retired by the paper's detection protocol, agreement across --dp replicas
  PYTHONPATH=src python -m repro.launch.serve --continuous \\
      --workload fixedpoint_solve --termination residual_interval \\
      --requests 8 --dp 3 --gen 400

Elastic serving (DESIGN.md S15): kill/join termination-agreement replicas
under live traffic — no request lost, no slot re-prefilled:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --continuous --slots 4 --requests 16 --arrival poisson:0.5 \\
      --dp 4 --elastic-policy grow_on_join --steps-per-dispatch 4 \\
      --kill 6:2 --join 16:4,5 --kill 26:0

Multi-tenant traffic + SLA autoscaling (DESIGN.md S17): named tenants with
TTFT SLAs / priorities / admission quotas, bursty or diurnal arrivals, and
an autoscaler trading replica-funded capacity against SLA pressure:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --continuous --slots 8 --requests 32 --scheduler sla_edf \\
      --tenants "chat:3:sla=8:prio=2,batch:1:quota=4:gen=24" \\
      --arrival bursty:0.2,2.0 --dp 2 --slots-per-replica 4 \\
      --autoscale --max-extent 4 --steps-per-dispatch 4
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.distributed import step as step_lib
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.train import build_mesh
from repro.models import transformer
from repro.serving import (
    TERMINATION,
    WORKLOADS,
    Request,
    ServeConfig,
    ServeEngine,
    get_scheduler,
    make_workload,
)
from repro.serving.tenants import (
    build_requests,
    make_arrival_ticks,
    parse_tenant_specs,
    quotas_of,
)


def _arrival_ticks(spec: str, n: int, seed: int) -> list[int]:
    """``none`` | ``poisson:RATE`` | ``bursty:BASE,PEAK[,RATE,LEN]`` |
    ``diurnal:PEAK,PERIOD[,FLOOR]`` | ``trace:FILE`` — see
    :mod:`repro.serving.tenants` (this wrapper maps spec errors to CLI
    exits and is what ``bench_serve.py`` imports)."""
    try:
        return make_arrival_ticks(spec, n, seed)
    except (ValueError, OSError) as e:
        raise SystemExit(f"--arrival {spec!r}: {e}")


class _CliChaosScript:
    """Chaos events parsed from ``--kill/--join/--stall/--unstall`` flags,
    fired against the :class:`repro.runtime.ElasticServeController` on its
    tick clock (same ``apply_due`` contract as ``tests/chaos.py``)."""

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: e[0])
        self.fired = 0

    def apply_due(self, ctl, tick: int):
        while self.fired < len(self.events) and self.events[self.fired][0] <= tick:
            t, name, a, kw = self.events[self.fired]
            print(f"# chaos @tick {tick}: {name}{a}")
            getattr(ctl, name)(*a, **kw)
            self.fired += 1


def _parse_chaos(args) -> _CliChaosScript | None:
    events = []
    for spec in args.kill or []:
        parts = spec.split(":")
        events.append((int(parts[0]), "kill", (int(parts[1]),),
                       {"silent": len(parts) > 2 and parts[2] == "silent"}))
    for spec in args.join or []:
        tick, _, ids = spec.partition(":")
        events.append((int(tick), "join",
                       (tuple(int(i) for i in ids.split(",")),), {}))
    for spec in args.stall or []:
        parts = spec.split(":")
        factor = float(parts[2]) if len(parts) > 2 else 10.0
        events.append((int(parts[0]), "stall", (int(parts[1]), factor), {}))
    for spec in args.unstall or []:
        tick, _, rid = spec.partition(":")
        events.append((int(tick), "unstall", (int(rid),), {}))
    return _CliChaosScript(events) if events else None


def _static_main(args, cfg, mesh):
    serve_step, rules = step_lib.make_serve_step(cfg, mesh)
    prefill_step, _ = step_lib.make_cached_prefill_step(cfg, mesh)

    with mesh:
        params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
        max_len = args.prompt_len + args.gen
        cache = transformer.init_cache(cfg, args.batch, max_len)
        prompt = jax.random.randint(
            jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len), 0, cfg.vocab
        )
        jstep = jax.jit(serve_step, donate_argnums=(2,))
        jprefill = jax.jit(prefill_step, donate_argnums=(2,))

        # fence before timing so we measure execution, not dispatch of the
        # param/cache initialization still in flight
        jax.block_until_ready((params, cache, prompt))

        # --- prefill phase (single scanned dispatch) ---
        t0 = time.perf_counter()
        logits, cache = jprefill(params, prompt, cache)
        jax.block_until_ready((logits, cache))
        dt_prefill = time.perf_counter() - t0

        # --- decode phase ---
        out = []
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        for i in range(args.gen):
            out.append(np.asarray(toks))
            logits, cache = jstep(params, toks, cache, jnp.int32(args.prompt_len + i))
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(toks)
        dt_decode = time.perf_counter() - t0

        pre_tok = args.batch * args.prompt_len
        dec_tok = args.batch * args.gen
        print(f"prefill: {pre_tok} tokens in {dt_prefill * 1e3:.1f} ms "
              f"({pre_tok / dt_prefill:.1f} tok/s)")
        print(f"decode:  {dec_tok} tokens in {dt_decode * 1e3:.1f} ms "
              f"({dec_tok / dt_decode:.1f} tok/s, "
              f"{dt_decode / args.gen * 1e3:.2f} ms/step)")
        print("sample token ids:", np.stack(out, 1)[0][:16].tolist())


def _continuous_main(args, cfg, mesh):
    rng = np.random.default_rng(args.seed)

    if args.workload in ("llm_decode", "llm_decode_paged"):
        max_len = args.max_len or (args.prompt_len + args.gen + 4)
        kw = {}
        if args.workload == "llm_decode_paged":
            bs = args.block_size
            max_len = ((max_len + bs - 1) // bs) * bs  # whole blocks
            kw = {"block_size": bs}
            if args.num_blocks:
                kw["num_blocks"] = args.num_blocks
        wl = make_workload(
            args.workload, cfg=cfg, mesh=mesh, slots=args.slots,
            max_len=max_len, max_prompt_len=args.prompt_len, seed=args.seed,
            **kw,
        )
        termination = args.termination or "eos_maxlen"
        if not args.tenants:
            arrivals = _arrival_ticks(args.arrival, args.requests, args.seed + 7)
            reqs = [
                Request(
                    id=i, arrival=arrivals[i],
                    prompt=rng.integers(0, cfg.vocab, size=int(rng.integers(1, args.prompt_len + 1))),
                    max_new=int(rng.integers(max(1, args.gen // 2), args.gen + 1)),
                    priority=int(rng.integers(0, 3)),
                    sla=int(rng.integers(4, 64)),
                )
                for i in range(args.requests)
            ]
    else:
        n = ((args.n + args.dp - 1) // args.dp) * args.dp  # dp-block divisible
        if n != args.n:
            print(f"# rounding --n {args.n} up to {n} (divisible by dp={args.dp})")
        args.n = n
        wl = make_workload(
            "fixedpoint_solve", solver=args.solver, n=args.n,
            slots=args.slots, dp=args.dp,
        )
        termination = args.termination or "residual_interval"
        if not args.tenants:
            arrivals = _arrival_ticks(args.arrival, args.requests, args.seed + 7)
            reqs = []
            for i in range(args.requests):
                v = rng.random(args.n).astype(np.float32)
                reqs.append(Request(
                    id=i, arrival=arrivals[i], payload=v / v.sum(),
                    max_new=args.gen, priority=int(rng.integers(0, 3)),
                    sla=int(rng.integers(50, 500)),
                ))

    quotas = None
    if args.tenants:
        try:
            tenants = parse_tenant_specs(args.tenants)
        except ValueError as e:
            raise SystemExit(f"--tenants: {e}")
        # single-engine CLI: every tenant targets the deployed --workload
        # (mixed-workload scenarios live in TenantScenario / bench_scale)
        tenants = tuple(
            dataclasses.replace(t, workload=args.workload) for t in tenants
        )
        reqs = build_requests(
            tenants, {args.workload: wl}, args.requests,
            args.arrival, args.seed + 7,
        )[args.workload]
        quotas = quotas_of(tenants)

    eng = ServeEngine(wl, ServeConfig(
        scheduler=args.scheduler, termination=termination,
        dp=args.dp, eps=args.eps, max_retries=args.max_retries,
        steps_per_dispatch=args.steps_per_dispatch,
        quotas=quotas,
        slots_per_replica=args.slots_per_replica or None,
    ))
    script = _parse_chaos(args)
    if args.autoscale or args.elastic_policy or script is not None:
        from repro.runtime import ElasticServeController

        policy = args.elastic_policy or "grow_on_join"
        if args.autoscale:
            from repro.runtime.policies import SlaAutoscalePolicy

            policy = SlaAutoscalePolicy(
                min_extent=args.min_extent, max_extent=args.max_extent,
            )
        ctl = ElasticServeController(
            eng, policy=policy, min_extent=args.min_extent,
        )
        res = ctl.run(reqs, events=script)
        for ev in ctl.resizes:
            print(f"# resize: {ev.kind} {ev.old_dp} -> {ev.new_dp} "
                  f"@tick {ev.step} ({ev.reason})")
    else:
        res = eng.run(reqs)
    s = eng.summary()
    print(f"{args.workload} x {args.scheduler} x {termination} (dp={args.dp}): "
          f"{s['completed']} requests in {s['ticks']} ticks / {s['wall_s']:.2f} s")
    print(f"  throughput {s['throughput_tok_s']:.1f} tok/s | occupancy "
          f"{s['occupancy']:.2f} | converged {s['converged']}/{s['completed']}")
    print(f"  TTFT p50/p95 {s['ttft_p50_ms']:.1f}/{s['ttft_p95_ms']:.1f} ms | "
          f"TPOT p50/p95 {s['tpot_p50_ms']:.2f}/{s['tpot_p95_ms']:.2f} ms")
    if s["sla_total"]:
        print(f"  SLA {s['sla_met']}/{s['sla_total']} met | goodput "
              f"{s['goodput_ok']} ({s['goodput_per_ktick']:.1f}/ktick) | "
              f"replica-ticks {s['replica_ticks']}")
    for name, t in sorted(s["tenants"].items()):
        print(f"  tenant {name}: {t['completed']} done, {t['tokens_out']} tok "
              f"| sla {t['sla_met']}/{t['sla_total']} | "
              f"ttft p99 {t['ttft_p99_ticks']:.0f} ticks")
    if s["resizes"] or s["retried"]:
        print(f"  resizes {s['resizes']} | capacity retries {s['retried']} "
              f"| final dp {eng.dp}")
    if hasattr(wl, "cache_bytes"):
        extra = (f" | prefix blocks saved {wl.prefix_saved_blocks}"
                 if hasattr(wl, "prefix_saved_blocks") else "")
        print(f"  cache {wl.cache_bytes / 2**20:.2f} MiB | forced-at-capacity "
              f"{s['forced_at_capacity']}{extra}")
    first = res[min(res)]
    tail = (first.output[:8].tolist() if first.output.dtype.kind == "i"
            else np.round(first.output[:4], 5).tolist())
    print(f"  request {first.id}: {first.n_tokens} tokens, "
          f"admit@{first.admit_tick} retire@{first.retire_tick}, head {tail}")
    return eng, wl, reqs


def main(argv=None):
    """Run the CLI; continuous serving returns ``(engine, workload,
    requests)`` for callers that check what was served (the per-request
    results are ``engine.results``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.list_archs(),
                    help="model arch (required unless --workload fixedpoint_solve)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="static batch size")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # continuous batching (repro.serving)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching ServeEngine")
    ap.add_argument("--slots", type=int, default=4, help="decode pool slots")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--scheduler", default="fcfs",
                    help="SCHEDULERS entry, optionally parameterized "
                         "(fcfs | priority | sla_edf | sla_edf:MAX_WAIT)")
    ap.add_argument("--workload", default="llm_decode", choices=sorted(WORKLOADS))
    ap.add_argument("--termination", default=None, choices=sorted(TERMINATION),
                    help="default: eos_maxlen (llm) / residual_interval (fixedpoint)")
    ap.add_argument("--arrival", default="none",
                    help="none | poisson:RATE (req/tick) | "
                         "bursty:BASE,PEAK[,RATE,LEN] | "
                         "diurnal:PEAK,PERIOD[,FLOOR] | trace:FILE (JSON ticks)")
    ap.add_argument("--tenants", default=None,
                    metavar="NAME:WEIGHT[:sla=..][:prio=..][:quota=..][:gen=..],...",
                    help="multi-tenant traffic model (serving/tenants.py); "
                         "requests are sampled per tenant instead of i.i.d.")
    ap.add_argument("--max-len", type=int, default=0,
                    help="pool cache length (0 = prompt+gen+margin)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="llm_decode_paged: tokens per cache block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="llm_decode_paged: physical blocks "
                         "(0 = contiguous-capacity parity)")
    ap.add_argument("--solver", default="d_iteration",
                    help="fixedpoint_solve: SOLVERS entry (affine payload)")
    ap.add_argument("--n", type=int, default=64, help="fixedpoint problem size")
    ap.add_argument("--eps", type=float, default=1e-6)
    ap.add_argument("--steps-per-dispatch", type=int, default=16,
                    help="ticks per fused device dispatch; chaos events "
                         "fire at dispatch boundaries, so a finer value "
                         "lands --kill/--join closer to their nominal "
                         "ticks")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="requeues granted to capacity-forced requests")
    # elastic serving (DESIGN.md S15): resize the agreement extent live
    ap.add_argument("--elastic-policy", default=None,
                    help="drive the engine through an ElasticServeController "
                         "(ELASTIC_POLICIES entry, e.g. grow_on_join)")
    ap.add_argument("--min-extent", type=int, default=1,
                    help="never shrink below this many replicas")
    # SLA autoscaling (DESIGN.md S17)
    ap.add_argument("--autoscale", action="store_true",
                    help="drive the engine with the sla_autoscale policy "
                         "(queue/SLA pressure grows, idle shrinks)")
    ap.add_argument("--max-extent", type=int, default=8,
                    help="autoscaler: never grow beyond this many replicas")
    ap.add_argument("--slots-per-replica", type=int, default=0,
                    help="capacity model: each replica funds this many pool "
                         "slots, so resizes change admission capacity "
                         "(0 = all slots usable at any extent)")
    ap.add_argument("--kill", action="append", metavar="TICK:REPLICA[:silent]",
                    help="kill a replica at TICK (repeatable); ':silent' "
                         "waits for the virtual heartbeat timeout")
    ap.add_argument("--join", action="append", metavar="TICK:ID[,ID...]",
                    help="replicas ask to join at TICK (repeatable)")
    ap.add_argument("--stall", action="append", metavar="TICK:REPLICA[:FACTOR]",
                    help="slow a replica's heartbeat step time (repeatable)")
    ap.add_argument("--unstall", action="append", metavar="TICK:REPLICA")
    ap.add_argument("--telemetry", default=None, metavar="SINK[:PATH]",
                    help="enable the obs subsystem (DESIGN.md S18): "
                         "null | jsonl[:f.jsonl] | csv[:f.csv] | "
                         "chrome_trace[:trace.json] (load in Perfetto / "
                         "chrome://tracing)")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    if args.telemetry:
        from repro import obs

        try:
            obs.configure(args.telemetry)
        except ValueError as e:
            raise SystemExit(f"--telemetry: {e}")

    try:
        get_scheduler(args.scheduler)
    except ValueError as e:
        raise SystemExit(str(e))
    needs_model = not (args.continuous and args.workload == "fixedpoint_solve")
    cfg = None
    if needs_model:
        if not args.arch:
            raise SystemExit("--arch is required for LLM serving")
        cfg = (
            registry.get_smoke_config(args.arch) if args.smoke
            else registry.get_config(args.arch)
        )
        if cfg.is_encoder_only:
            raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    # continuous serving simulates the --dp agreement replicas (stacked
    # termination contributions), so the device mesh only needs the TP
    # extent; the static path shards the batch over real dp devices
    mesh_dp = 1 if args.continuous else args.dp
    mesh = build_mesh(mesh_dp, args.tp) if needs_model else None

    try:
        if args.continuous:
            return _continuous_main(args, cfg, mesh)
        _static_main(args, cfg, mesh)
    finally:
        if args.telemetry:
            from repro import obs

            t = obs.shutdown()
            sink = obs.telemetry().sink
            dest = getattr(sink, "path", None)
            print(f"# telemetry[{t['sink']}]: {t['spans']} spans, "
                  f"{t['instants']} instants, "
                  f"{t['events_dropped'] + t['metrics_dropped']} dropped"
                  + (f" -> {dest}" if dest else ""))


if __name__ == "__main__":
    main()
