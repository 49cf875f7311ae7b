"""Grad-sync strategy ``mrd_leaf``: bucketed MRD butterfly gradient
allreduce with a tree-shaped optimizer (beyond-paper iteration on
``mrd_paper``).

Historically this mode ran one full schedule cycle *per gradient leaf*,
paying the per-message alpha cost once per tensor.  It now packs the
gradient tree into dtype-homogeneous, size-capped buckets and executes
the butterfly stage-major across them
(:meth:`repro.collectives.plans.CollectivePlan.run_bucketed`,
DESIGN.md S10) — leaf dtypes are preserved end-to-end and the per-leaf
loop is gone.  Trade-off vs the old per-leaf path: packing concatenates
leaves, so on partial-manual runtimes TP-sharded grads are gathered
over the auto "model" axis before the DP butterfly (the per-leaf path
moved 1/tp of each leaf with no reshard); tune ``bucket_bytes`` or
prefer ``mrd_zero1`` when TP resharding dominates.  Optimizer: fp32
tree, TP-sharded via param specs, DP-replicated (memory
~16 B/param / tp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.collectives import buckets, plans
from repro.distributed import sharding as shd
from repro.distributed.gradsync import common, register, register_resize
from repro.distributed.gradsync import overlap as overlap_lib
from repro.distributed.gradsync.common import TrainConfig
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.optim import optimizer as opt_lib


@register("mrd_leaf")
def make(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    rules = shd.make_rules(cfg, mesh, fsdp=False)
    remat_policy = common.REMAT_POLICIES[tcfg.remat]
    pdt = dtype_of(cfg.param_dtype)
    executor = common.resolve_executor(tcfg)
    dp_axes = rules.dp_axes
    dp = rules.dp
    monitor = common.build_monitor(tcfg, rules)
    grad_ar = plans.allreduce_plan(
        schedule="mrd", axes=dp_axes, op="sum", executor=executor
    )
    if tcfg.overlap:
        # ready-bucket overlap (DESIGN.md S16): prebuild the same fp32
        # layout run_bucketed would derive from the gradient tree, so the
        # overlapped path is bit-identical by construction
        pshape = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
        )
        fp32 = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(tuple(l.shape), jnp.float32), pshape
        )
        layout = buckets.build_layout(
            fp32, bucket_bytes=tcfg.bucket_bytes, quantum=grad_ar.pad_quantum()
        )
        koffs = overlap_lib.key_offsets(pshape)
        bgroups = overlap_lib.bucket_groups(
            layout, overlap_lib.leaf_groups(pshape)
        )

    def init_state(key):
        params = transformer.init_params(cfg, key)
        state = {
            "params": params,
            "opt": opt_lib.init_opt_state(params),
            "step": jnp.zeros((), jnp.int32),
        }
        if monitor is not None:
            state["monitor"] = common.monitor_rows_init(monitor, dp)
        return state

    def state_specs(state):
        pspecs = shd.param_specs(cfg, rules, state["params"])
        specs = {
            "params": pspecs,
            "opt": {"master": pspecs, "mu": pspecs, "nu": pspecs},
            "step": P(),
        }
        if monitor is not None:
            specs["monitor"] = jax.tree.map(lambda _: P(dp_axes), state["monitor"])
        return specs

    def train_step(state, batch):
        def local_step(params, opt, step, mon_state, local_batch):
            if tcfg.overlap:
                # segmented backward, ready buckets issued mid-backward
                # through the same butterfly (DESIGN.md S16)
                with shd.sharding_ctx(cfg, common.manual_rules(rules)):
                    emitter = overlap_lib.segmented_grads(
                        params, local_batch, cfg, remat_policy,
                        tcfg.microbatches,
                    )
                    loss, metrics, red, _ = overlap_lib.drive(
                        emitter, layout, koffs, bgroups, plan=grad_ar
                    )
                grads = buckets.unpack(red, layout)
            else:
                with shd.sharding_ctx(cfg, common.manual_rules(rules)):
                    grads, loss, metrics = common.microbatched_grads(
                        params, local_batch, cfg, remat_policy, tcfg.microbatches
                    )
                # the paper's butterfly, pipelined over dtype-homogeneous
                # gradient buckets (stage-major; DESIGN.md S10)
                grads = grad_ar.run_bucketed(grads, bucket_bytes=tcfg.bucket_bytes)
            grads = jax.tree.map(lambda g: g / dp, grads)
            grads, gnorm = opt_lib.clip_by_global_norm(grads, tcfg.optimizer.grad_clip)
            params, opt = opt_lib.apply_update(
                grads, opt, tcfg.optimizer, step, pdt
            )
            mon_out, done, val = common.local_monitor_tick(
                monitor, mon_state, metrics["per_example"].mean(), step
            )
            return params, opt, mon_out, loss[None], gnorm[None], done, val

        dpP = P(dp_axes)
        bspecs = common.batch_specs(cfg, rules, batch)
        if monitor is not None:
            mon_state_in = state["monitor"]
            mon_spec = jax.tree.map(lambda _: dpP, state["monitor"])
        else:
            mon_state_in = jnp.zeros((dp, 1), jnp.float32)
            mon_spec = dpP
        rep = lambda t: jax.tree.map(lambda _: P(), t)
        out = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(rep(state["params"]), rep(state["opt"]), P(), mon_spec, bspecs),
            out_specs=(rep(state["params"]), rep(state["opt"]), mon_spec, dpP, dpP, dpP, dpP),
            axis_names=set(dp_axes),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], mon_state_in, batch)
        params, opt, mon, loss, gnorm, done, val = out
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        if monitor is not None:
            new_state["monitor"] = mon
        return new_state, {
            "loss": loss.mean(),
            "grad_norm": gnorm[0],
            "converged": done[0],
            "monitor_value": val[0],
        }

    return train_step, init_state, state_specs, rules


@register_resize("mrd_leaf")
def resize(cfg, tcfg, old_mesh, new_mesh, state, keep):
    """Elastic resize: the tree-shaped optimizer is DP-replicated, so any
    survivor's copy is the state; only the monitor rows re-lay-out."""
    new_state = dict(state)
    if "monitor" in state:
        rules_n = shd.make_rules(cfg, new_mesh, fsdp=False)
        new_state["monitor"] = common.monitor_rows_migrate(
            tcfg, rules_n, state["monitor"], keep
        )
    return new_state
