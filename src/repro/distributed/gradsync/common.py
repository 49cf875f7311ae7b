"""Shared building blocks for grad-sync strategies (DESIGN.md S2).

Every strategy module composes the same pieces: microbatched gradient
accumulation, remat policy, the paper's ConvergenceMonitor (advanced one
MRD stage per train step — one scalar ppermute, never blocking), and the
optimizer.  Strategies differ only in *how the gradient crosses the DP
axes and where the optimizer state lives* — that difference is what each
``repro.distributed.gradsync`` module encodes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.asynchrony import ConvergenceMonitor
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import optimizer as opt_lib

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: str = "full"  # 'none' | 'full' | 'dots'
    # any name in repro.distributed.gradsync.GRAD_SYNC ('gspmd', 'mrd_paper',
    # 'mrd_leaf', 'mrd_zero1', 'compressed', 'local_sgd', ...)
    grad_sync: str = "gspmd"
    local_sync_every: int = 8  # local_sgd: MRD param-average period (staleness bound)
    monitor: bool = True
    # any repro.asynchrony.DETECTION_PROTOCOLS entry with a training-loop
    # policy: 'inexact' (Alg.1) | 'exact' (Alg.2) | 'interval' (windowed)
    monitor_mode: str = "inexact"
    monitor_threshold: float = 1e-3
    # EF-SGD error feedback for quantized grad sync ('compressed'): carry the
    # per-shard quantization residual and fold it into the next step's
    # gradient.  Ignored by identity-transform modes.
    error_feedback: bool = True
    optimizer: opt_lib.OptimizerConfig = dataclasses.field(
        default_factory=opt_lib.OptimizerConfig
    )
    fsdp: bool = True  # weight sharding over "data" (gspmd mode)
    # collectives executor for the MRD strategies: None = auto ('device';
    # 'device_fused' routes the int8 combine through the Pallas kernel)
    collective_executor: Optional[str] = None
    # cap on each dtype-homogeneous gradient bucket for the pipelined
    # collective engine (repro.collectives.buckets, DESIGN.md S10);
    # None = one unbounded bucket per dtype
    bucket_bytes: Optional[int] = 32 * 2**20
    # ready-bucket grad-sync overlap (DESIGN.md S16): issue each gradient
    # bucket's MRD stages as its backward segment completes instead of
    # after the full backward.  Bit-identical results by construction
    # (same BucketLayout, only issue order changes).  Honored by the
    # gradient-scale modes (mrd_leaf, mrd_paper, mrd_zero1, compressed);
    # gspmd/local_sgd have no bucketed gradient path and ignore it.
    overlap: bool = False


def manual_rules(rules: shd.ShardingRules) -> shd.ShardingRules:
    """Rules for a strategy's shard_map body (manual over the DP axes only,
    so TP constraints on the auto "model" axis stay live)."""
    return rules.manual_region()


def resolve_executor(tcfg: TrainConfig, *, compressed: bool = False) -> str:
    if tcfg.collective_executor is not None:
        return tcfg.collective_executor
    if compressed and jax.default_backend() == "tpu":
        return "device_fused"
    return "device"


# ---------------------------------------------------------------------------
# Gradients
# ---------------------------------------------------------------------------


def batch_specs(cfg: ModelConfig, rules: shd.ShardingRules, batch: Any):
    """PartitionSpecs for a train batch pytree (batch dim over DP axes)."""

    def spec(leaf):
        b = rules.batch_axes(leaf.shape[0])
        return P(b, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch)


def microbatched_grads(params, batch, cfg, remat_policy, microbatches: int):
    """Gradient accumulation over microbatches via lax.scan (fp32 accum).
    Returns (grads_fp32, mean_loss, metrics_last)."""

    def loss_fn(p, mb):
        return transformer.forward_train(p, mb, cfg, remat_policy)

    if microbatches == 1:
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return jax.tree.map(lambda x: x.astype(jnp.float32), g), loss, metrics

    def reshape_mb(x):
        return x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:])

    mbs = jax.tree.map(
        lambda x: shd.constrain(reshape_mb(x), "mb_batch"), batch
    )
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        g_acc, loss_acc = carry
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
        return (g_acc, loss_acc + loss), metrics

    (g, loss_sum), metrics = jax.lax.scan(body, (g0, 0.0), mbs, unroll=cfg.scan_unroll)
    g = jax.tree.map(lambda x: x / microbatches, g)
    metrics = jax.tree.map(lambda x: x[-1], metrics)
    return g, loss_sum / microbatches, metrics


# ---------------------------------------------------------------------------
# Monitor wiring (identical across strategies)
# ---------------------------------------------------------------------------


def build_monitor(tcfg: TrainConfig, rules: shd.ShardingRules):
    """The paper's staged detector over the DP domain, or None."""
    if not tcfg.monitor:
        return None
    axes = rules.dp_axes
    return ConvergenceMonitor(
        axis_name=axes if len(axes) > 1 else axes[0],
        threshold=tcfg.monitor_threshold,
        mode=tcfg.monitor_mode,
    )


def monitor_rows_init(monitor: Optional[ConvergenceMonitor], dp: int):
    """Replicated-then-sharded monitor state: one row per DP rank."""
    mon = monitor.init(varying=False)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (dp,) + x.shape), mon)


def monitor_rows_migrate(tcfg: TrainConfig, rules, rows, keep):
    """Elastic resize of the ``state['monitor']`` rows (or None pass-through):
    surviving rows follow their workers, joiners get fresh rows, and the
    staged reduction restarts (see
    :meth:`repro.asynchrony.ConvergenceMonitor.migrate_rows`)."""
    monitor = build_monitor(tcfg, rules)
    if monitor is None or rows is None:
        return rows
    return monitor.migrate_rows(rows, keep)


def local_monitor_tick(monitor, mon_state, metric, step):
    """Inside shard_map: advance this rank's monitor row ([1, ...] leaves).

    Returns (new rows, done [1], value [1]); zeros when monitor is None.
    """
    if monitor is None:
        return mon_state, jnp.zeros((1,), jnp.bool_), jnp.zeros((1,), jnp.float32)
    local = jax.tree.map(lambda x: x[0], mon_state)
    new, done, val = monitor.step(local, metric, step)
    return jax.tree.map(lambda x: x[None], new), done[None], val[None]
