"""Training driver.

Usage (CPU demo sizes):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \\
      --steps 50 --batch 8 --seq 64 --grad-sync mrd_zero1

On a real cluster the same driver runs the full config on the production
mesh (remove --smoke); the dry-run (launch/dryrun.py) proves those programs
compile and fit.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro import obs
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import gradsync
from repro.distributed import step as step_lib
from repro.launch.compile_cache import enable_compilation_cache
from repro.optim.optimizer import OptimizerConfig


def build_mesh(dp: int, tp: int):
    """A mesh over the first ``dp * tp`` devices in their listed order.

    ``jax.make_mesh`` reorders by TPU topology and refuses a subset that is
    not a whole sub-torus, such as 3 chips of a 2x2 host: the
    non-power-of-two extents the paper is about need the plain order.
    """
    axes = ("data", "model") if tp > 1 else ("data",)
    shape = (dp, tp) if tp > 1 else (dp,)
    devices = np.asarray(jax.devices()[: dp * tp]).reshape(shape)
    return Mesh(devices, axes)


def main(argv=None):
    """Run the CLI; returns the per-step training losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers of the config, widths "
                         "unchanged (0 = all): a depth cut that fits the "
                         "training state on fewer chips")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-sync", default="gspmd",
                    choices=gradsync.available())
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd", "const"])
    ap.add_argument("--monitor-threshold", type=float, default=0.0,
                    help="stop when the staged-MRD-certified loss < threshold")
    ap.add_argument("--monitor-mode", default="inexact",
                    choices=["inexact", "exact", "interval"])
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the EF-SGD residual carry of the "
                         "'compressed' grad-sync mode")
    ap.add_argument("--overlap", action="store_true", default=False,
                    help="ready-bucket grad-sync overlap (DESIGN.md S16): "
                         "issue each gradient bucket's MRD stages as its "
                         "backward segment completes; bit-identical to the "
                         "post-backward path (gradient-scale modes only)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="post-backward bucketed grad sync (the default)")
    ap.add_argument("--no-donate", action="store_true",
                    help="never donate the train state to jit (donation is "
                         "already skipped on CPU, where it deadlocks "
                         "shard_map strategies like mrd_leaf)")
    ap.add_argument("--elastic-policy", default=None,
                    help="drive training through the elastic runtime with "
                         "this resize policy (any ELASTIC_POLICIES entry: "
                         "static | shrink_on_failure | grow_on_join | "
                         "drain_straggler); default: plain train loop")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-every-seconds", type=float, default=None,
                    help="also snapshot whenever this much wall time has "
                         "passed since the last save (time-based policy; "
                         "combines with --ckpt-every, whichever fires first)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--bucket-bytes", type=int, default=32 * 2**20,
                    help="cap per gradient bucket for the pipelined "
                         "collective engine (0 = one bucket per dtype)")
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
        return _main(args)
    finally:
        if args.telemetry:
            from repro import obs

            t = obs.shutdown()
            dest = getattr(obs.telemetry().sink, "path", None)
            print(f"# telemetry[{t['sink']}]: {t['spans']} spans, "
                  f"{t['instants']} instants, "
                  f"{t['events_dropped'] + t['metrics_dropped']} dropped"
                  + (f" -> {dest}" if dest else ""))


def _main(args):

    cfg = (
        registry.get_smoke_config(args.arch) if args.smoke else registry.get_config(args.arch)
    )
    if args.layers:
        if not 0 < args.layers <= cfg.n_layers:
            raise SystemExit(f"--layers {args.layers}: {cfg.name} has {cfg.n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    mesh = build_mesh(args.dp, args.tp)
    tcfg = step_lib.TrainConfig(
        microbatches=args.microbatches,
        remat=args.remat,
        grad_sync=args.grad_sync,
        monitor=args.monitor_threshold > 0,
        monitor_mode=args.monitor_mode,
        monitor_threshold=args.monitor_threshold,
        error_feedback=not args.no_error_feedback,
        overlap=args.overlap,
        bucket_bytes=args.bucket_bytes or None,
        optimizer=OptimizerConfig(
            lr=args.lr, schedule=args.schedule,
            warmup_steps=min(20, args.steps // 10),
            total_steps=args.steps,
        ),
    )
    ck = (
        Checkpointer(
            args.ckpt_dir,
            save_every_steps=args.ckpt_every,
            save_every_seconds=args.ckpt_every_seconds,
        )
        if args.ckpt_dir
        else None
    )

    if args.elastic_policy is not None:
        # policy-driven elastic runtime (DESIGN.md S12): failures shrink the
        # DP extent in place, joiners grow it; the MRD collectives keep every
        # resulting (non-power-of-two) extent correct.
        from repro.data.pipeline import DataConfig as _DC
        from repro.runtime import ElasticConfig, ElasticTrainer, get_policy

        get_policy(args.elastic_policy)  # fail fast on unknown names
        trainer = ElasticTrainer(
            mesh, (cfg, tcfg),
            pipe_factory=lambda m: SyntheticPipeline(
                cfg, _DC(batch=args.batch, seq_len=args.seq, seed=args.seed), m
            ),
            checkpointer=ck,
            cfg=ElasticConfig(
                ckpt_every=args.ckpt_every, policy=args.elastic_policy
            ),
        )
        state = trainer.init_or_restore(jax.random.PRNGKey(args.seed))
        state, losses = trainer.run(state, args.steps)
        print(
            f"done ({len(trainer.resizes)} resizes, {trainer.restores} "
            f"checkpoint restores). final loss: {losses[-1]:.4f}"
        )
        return [float(l) for l in losses]

    train_step, init_state, state_specs, rules = step_lib.make_train_step(cfg, mesh, tcfg)

    with mesh:
        # initialize straight into the shardings: an eager init would build
        # the whole float32 train state on the first device before moving it
        key = jax.random.PRNGKey(args.seed)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            state_specs(jax.eval_shape(init_state, key)),
        )
        state = jax.jit(init_state, out_shardings=shardings)(key)
        pipe = SyntheticPipeline(
            cfg, DataConfig(batch=args.batch, seq_len=args.seq, seed=args.seed), mesh
        )
        if ck is not None and ck.latest_step() is not None:
            step0 = ck.latest_step()
            state = ck.restore(step0, jax.tree.map(np.asarray, jax.device_get(state)), shardings)
            pipe.load_state_dict(ck.manifest(step0)["extra"]["data"])
            print(f"resumed from checkpoint step {step0}")
        # Donating the state saves a copy on accelerators, but on multi-device
        # CPU the DP-replicated params of the shard_map strategies (mrd_leaf &
        # co) share one backing buffer across devices; donating it raises
        # "Attempt to donate the same buffer twice in Execute()" on one
        # replica while the others block forever at the collective-permute
        # rendezvous — the historical mrd_leaf "deadlock".  Donation buys
        # nothing on CPU anyway, so gate it on the backend.
        donate = (0,) if jax.default_backend() != "cpu" and not args.no_donate else ()
        jstep = jax.jit(train_step, donate_argnums=donate)
        # async snapshots: with donation on, the next jstep call deletes the
        # state's buffers, so the save must at least finish the d2h transfer
        # ('transfer'); without donation the buffers stay alive and the save
        # can be fully fire-and-forget
        save_block = "transfer" if donate else False

        t0 = time.time()
        losses = []
        for i in range(args.steps):
            with obs.span("train.step", step=i):
                state, metrics = jstep(state, pipe.next_batch())
            losses.append(metrics["loss"])
            if obs.enabled():
                # the loss is a device array still in flight: the gauge
                # stores the reference, the writer thread materializes it
                # at drain — no dispatch fence on the train loop
                obs.gauge("train.loss").set(metrics["loss"])
                obs.counter("train.steps").add(1)
            if (i + 1) % args.log_every == 0 or i == 0:
                print(
                    f"step {int(state['step'])}: loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"({(time.time()-t0)/(i+1)*1e3:.0f} ms/step)"
                )
            if ck is not None and ck.should_save(i + 1):
                # pipe.state_dict() is captured *now*, in the same host
                # instant the state leaves are staged — snapshot and data
                # cursor stay consistent even though the write is async
                ck.save(
                    int(state["step"]), state,
                    extra={"data": pipe.state_dict()}, block=save_block,
                )
            if tcfg.monitor and bool(metrics["converged"]):
                obs.instant(
                    "monitor.certify",
                    mode=args.monitor_mode,
                    step=int(state["step"]),
                    value=float(metrics["monitor_value"]),
                )
                print(
                    f"ConvergenceMonitor ({args.monitor_mode}) certified "
                    f"loss {float(metrics['monitor_value']):.4f} < "
                    f"{args.monitor_threshold} at step {int(state['step'])} — stopping."
                )
                break
        if ck is not None:
            ck.save(int(state["step"]), state, extra={"data": pipe.state_dict()}, block=True)
    print("done. final loss:", float(metrics["loss"]))
    return [float(l) for l in losses]


if __name__ == "__main__":
    main()
