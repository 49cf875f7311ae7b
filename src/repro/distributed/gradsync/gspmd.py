"""Grad-sync strategy ``gspmd``: pure pjit baseline.

Params FSDP+TP sharded; XLA inserts the DP all-reduce in backward.  The
ConvergenceMonitor still advances the paper's staged MRD detection — one
scalar ppermute per step inside a tiny shard_map over the DP axes.

``tcfg.overlap`` is a no-op here: there is no explicit bucketed gradient
path to reorder — XLA's latency-hiding scheduler already interleaves its
own all-reduces with backward compute (DESIGN.md S16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.gradsync import common, register, register_resize
from repro.distributed.gradsync.common import TrainConfig
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.optim import optimizer as opt_lib


@register("gspmd")
def make(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    """Returns (jitted step, init_state_fn, state_shardings_fn, rules)."""
    rules = shd.make_rules(cfg, mesh, fsdp=tcfg.fsdp)
    remat_policy = common.REMAT_POLICIES[tcfg.remat]
    pdt = dtype_of(cfg.param_dtype)
    monitor = common.build_monitor(tcfg, rules)
    dp = rules.dp

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
            "opt": {
                "master": pspecs,
                "mu": pspecs,
                "nu": pspecs,
            },
            "step": P(),
        }
        if monitor is not None:
            specs["monitor"] = jax.tree.map(
                lambda x: P(rules.dp_axes), state["monitor"]
            )
        return specs

    def train_step(state, batch):
        with shd.sharding_ctx(cfg, rules):
            grads, loss, metrics = common.microbatched_grads(
                state["params"], batch, cfg, remat_policy, tcfg.microbatches
            )
        grads, gnorm = opt_lib.clip_by_global_norm(grads, tcfg.optimizer.grad_clip)
        params, opt = opt_lib.apply_update(
            grads, state["opt"], tcfg.optimizer, state["step"], pdt
        )
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        out_metrics = {"loss": loss, "grad_norm": gnorm}

        if monitor is not None:
            # per-DP-shard local loss feeds the paper's staged detection
            def mon_fn(mon_st, per_ex, step):
                return common.local_monitor_tick(
                    monitor, mon_st, per_ex.mean(), step
                )

            # per_example is [B/microbatches]; when that no longer divides
            # the DP extent (large mb on the multi-pod mesh), feed it
            # replicated — each worker then monitors the same global mean,
            # which stays sound (the staged reduction just becomes uniform).
            pe_spec = P(rules.batch_axes(metrics["per_example"].shape[0]))
            mon_new, done, val = jax.shard_map(
                mon_fn,
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(rules.dp_axes), state["monitor"]),
                    pe_spec,
                    P(),
                ),
                out_specs=(
                    jax.tree.map(lambda _: P(rules.dp_axes), state["monitor"]),
                    P(rules.dp_axes),
                    P(rules.dp_axes),
                ),
                axis_names=set(rules.dp_axes),
                check_vma=False,
            )(state["monitor"], metrics["per_example"], state["step"])
            new_state["monitor"] = mon_new
            out_metrics["converged"] = done[0]
            out_metrics["monitor_value"] = val[0]
        return new_state, out_metrics

    return train_step, init_state, state_specs, rules


@register_resize("gspmd")
def resize(cfg, tcfg, old_mesh, new_mesh, state, keep):
    """Elastic resize: params/opt are mesh-shape-independent global arrays
    (XLA re-partitions them under the new mesh's shardings); only the
    per-DP-rank monitor rows need re-laying-out."""
    new_state = dict(state)
    if "monitor" in state:
        rules_n = shd.make_rules(cfg, new_mesh, fsdp=tcfg.fsdp)
        new_state["monitor"] = common.monitor_rows_migrate(
            tcfg, rules_n, state["monitor"], keep
        )
    return new_state
