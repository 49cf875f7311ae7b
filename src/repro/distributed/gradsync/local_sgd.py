"""Grad-sync strategy ``local_sgd``: bounded-staleness local SGD
(asynchronous-iterations-inspired; DESIGN.md S9).

Each DP worker trains its own replica with purely local gradients for
``local_sync_every`` steps, then replicas are averaged by the paper's
collectives (one chained Rabenseifner RS+AG plan over the flat vector).
Stragglers never block intermediate steps; the staleness bound plays the
role of the paper's bounded retards.  Per-replica state costs dp x the
replicated-params memory — pair with TP for larger models.

``tcfg.overlap`` is a no-op here: gradients never cross the DP axes
(only the periodic param average does), so there is no per-step bucketed
reduction to overlap with the backward (DESIGN.md S16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.collectives import plans
from repro.distributed import sharding as shd
from repro.distributed.gradsync import common, register, register_resize
from repro.distributed.gradsync.common import TrainConfig
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.optim import optimizer as opt_lib


@register("local_sgd")
def make(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    rules = shd.make_rules(cfg, mesh, fsdp=False)
    remat_policy = common.REMAT_POLICIES[tcfg.remat]
    pdt = dtype_of(cfg.param_dtype)
    executor = common.resolve_executor(tcfg)
    dp_axes = rules.dp_axes
    dp = rules.dp
    H = max(tcfg.local_sync_every, 1)

    def init_state(key):
        params = transformer.init_params(cfg, key)
        rep = lambda x: jnp.broadcast_to(x[None], (dp,) + x.shape)
        return {
            "params": jax.tree.map(rep, params),
            "opt": jax.tree.map(rep, opt_lib.init_opt_state(params)),
            "step": jnp.zeros((), jnp.int32),
        }

    def state_specs(state):
        dpP_tree = lambda t: jax.tree.map(lambda _: P(dp_axes), t)
        return {
            "params": dpP_tree(state["params"]),
            "opt": dpP_tree(state["opt"]),
            "step": P(),
        }

    def train_step(state, batch):
        def local_step(params_s, opt_s, step, local_batch):
            params = jax.tree.map(lambda x: x[0], params_s)
            opt = jax.tree.map(lambda x: x[0], opt_s)
            with shd.sharding_ctx(cfg, common.manual_rules(rules)):
                grads, loss, metrics = common.microbatched_grads(
                    params, local_batch, cfg, remat_policy, tcfg.microbatches
                )
            grads, gnorm = opt_lib.clip_by_global_norm(grads, tcfg.optimizer.grad_clip)
            params, opt = opt_lib.apply_update(
                grads, opt, tcfg.optimizer, step, pdt
            )

            def sync(ps):
                # the paper's collectives: average the replicas across the
                # whole DP domain with a chained RS+AG plan, pipelined over
                # size-capped param buckets (DESIGN.md S10)
                avg = plans.tree_allreduce(
                    jax.tree.map(lambda x: x.astype(jnp.float32), ps),
                    schedule="rabenseifner",
                    axes=dp_axes,
                    executor=executor,
                    bucket_bytes=tcfg.bucket_bytes,
                )
                return jax.tree.map(
                    lambda a, b: (a / dp).astype(b.dtype), avg, ps
                )

            do_sync = (step + 1) % H == 0
            params = jax.lax.cond(do_sync, sync, lambda q: q, params)
            add1 = lambda t: jax.tree.map(lambda x: x[None], t)
            return add1(params), add1(opt), loss[None], gnorm[None]

        dpP = P(dp_axes)
        dpP_tree = lambda t: jax.tree.map(lambda _: dpP, t)
        bspecs = common.batch_specs(cfg, rules, batch)
        params_s, opt_s, loss, gnorm = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(dpP_tree(state["params"]), dpP_tree(state["opt"]), P(), bspecs),
            out_specs=(dpP_tree(state["params"]), dpP_tree(state["opt"]), dpP, dpP),
            axis_names=set(dp_axes),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], batch)
        new_state = {"params": params_s, "opt": opt_s, "step": state["step"] + 1}
        return new_state, {
            "loss": loss.mean(),
            "grad_norm": gnorm.mean(),
            "converged": jnp.zeros((), jnp.bool_),
            "monitor_value": jnp.zeros((), jnp.float32),
        }

    return train_step, init_state, state_specs, rules


@register_resize("local_sgd")
def resize(cfg, tcfg, old_mesh, new_mesh, state, keep):
    """Elastic resize: params/opt are dp-major replica rows.  Surviving
    replicas follow their workers; a joiner clones the first survivor's
    replica (it has no local history of its own — the next
    ``local_sync_every`` boundary folds it into the average anyway)."""
    src = next(k for k in keep if k is not None)

    def sel(rows):
        return jnp.stack([rows[k if k is not None else src] for k in keep])

    new_state = dict(state)
    new_state["params"] = jax.tree.map(sel, state["params"])
    new_state["opt"] = jax.tree.map(sel, state["opt"])
    return new_state
