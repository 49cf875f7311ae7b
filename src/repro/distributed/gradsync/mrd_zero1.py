"""Grad-sync strategy ``mrd_zero1``: the paper's butterfly as a ZeRO-1
distributed optimizer (beyond-paper).

Inside ``shard_map`` (manual over the DP axes, auto over "model"): the
flat fp32 gradient is packed into size-capped buckets
(:mod:`repro.collectives.buckets`), each bucket reduce-scattered over the
DP axes with the recursive-halving schedule **stage-major across buckets**
(:meth:`repro.collectives.plans.CollectivePlan.run_buffers`, DESIGN.md
S10) so collective-permute overlaps neighbouring buckets' compute;
shard-local AdamW runs on the concatenated per-bucket fp32 segments, then
the bf16 params all-gather back per bucket on the same pipelined path.
Works for non-power-of-two DP groups (the paper's headline case) — the
elasticity path uses exactly this.  Hierarchy is implicit: with mesh axes
("pod","data"), the chained RS/AG reduces inter-pod bytes by 1/p0(data).

All collectives run through :class:`repro.collectives.plans.CollectivePlan`;
``mrd_paper`` and ``compressed`` reuse this builder with a different
schedule/transform binding.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.collectives import buckets, plans
from repro.collectives.schedules import pivot
from repro.distributed import sharding as shd
from repro.distributed.gradsync import common, register, register_resize
from repro.distributed.gradsync import overlap as overlap_lib
from repro.distributed.gradsync.common import TrainConfig
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.optim import optimizer as opt_lib


def zero1_prod_p0(mesh: Mesh, dp_axes) -> int:
    """Product of the per-axis pivot sizes (live RS segment count)."""
    prod_p0 = 1
    for ax in dp_axes:
        p0, _, _ = pivot(mesh.shape[ax])
        prod_p0 *= p0
    return prod_p0


def zero1_shard_len(n_params: int, mesh: Mesh, dp_axes, block: int = 256) -> tuple[int, int]:
    """(padded_total, shard_len) for a *single-bucket* chained RS over
    dp_axes (legacy flat layout; the bucketed layout generalizes this
    per bucket — see :func:`zero1_layout`)."""
    prod_p0 = zero1_prod_p0(mesh, dp_axes)
    quantum = prod_p0 * block
    padded = ((n_params + quantum - 1) // quantum) * quantum
    return padded, padded // prod_p0


def zero1_layout(
    pshape,
    mesh: Mesh,
    dp_axes,
    *,
    bucket_bytes: Optional[int] = buckets.DEFAULT_BUCKET_BYTES,
    block: int = 256,
) -> tuple[buckets.BucketLayout, int]:
    """(bucket layout, prod_p0) for the bucketed chained RS over dp_axes.

    The layout is built over the fp32 view of ``pshape`` (gradients are
    accumulated in fp32); every bucket is padded to ``prod_p0 * block``
    elements so each RS phase divides evenly and int8 blocks stay aligned.
    Master/moment rows are the per-bucket owned segments concatenated in
    bucket order — total shard length ``layout.total_padded / prod_p0``.
    """
    prod_p0 = zero1_prod_p0(mesh, dp_axes)
    fp32 = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(tuple(l.shape), jnp.float32), pshape
    )
    layout = buckets.build_layout(
        fp32, bucket_bytes=bucket_bytes, quantum=prod_p0 * block
    )
    return layout, prod_p0


def zero1_masters_from_params(
    params,
    mesh: Mesh,
    dp_axes,
    *,
    bucket_bytes: Optional[int] = buckets.DEFAULT_BUCKET_BYTES,
    paper_mode: bool = False,
) -> jnp.ndarray:
    """``[dp, m]`` fp32 master rows matching :func:`make_zero1`'s bucketed
    shard layout — the elastic restart path re-seeds masters from restored
    params with exactly this (tests/test_fault_tolerance.py)."""
    layout, prod_p0 = zero1_layout(params, mesh, dp_axes, bucket_bytes=bucket_bytes)
    bufs = buckets.pack(
        jax.tree.map(lambda x: x.astype(jnp.float32), params), layout
    )
    dp = int(np.prod([mesh.shape[ax] for ax in dp_axes]))
    if paper_mode:
        flat = jnp.concatenate(bufs) if bufs else jnp.zeros((0,), jnp.float32)
        return jnp.broadcast_to(flat, (dp, flat.shape[0]))
    owners = zero1_owner_segments(mesh, dp_axes)
    seg_bufs = [b.reshape(prod_p0, -1) for b in bufs]
    m = layout.total_padded // prod_p0
    rows = [
        jnp.concatenate([sb[o] for sb in seg_bufs]) if o is not None
        else jnp.zeros((m,), jnp.float32)
        for o in owners
    ]
    return jnp.stack(rows)


def zero1_gather_buckets(rows, layout, owners, prod_p0: int) -> list:
    """Reassemble full per-bucket buffers from owner-sharded ``[dp, m]`` rows
    (each owner row concatenates its per-bucket segments in bucket order) —
    the inverse of the scatter in :func:`zero1_masters_from_params`."""
    rank_of = {seg: r for r, seg in enumerate(owners) if seg is not None}
    bufs, shard_off = [], 0
    for blen in layout.bucket_lengths:
        seg = blen // prod_p0
        bufs.append(
            jnp.concatenate(
                [rows[rank_of[s], shard_off : shard_off + seg]
                 for s in range(prod_p0)]
            )
        )
        shard_off += seg
    return bufs


def zero1_scatter_buckets(bufs, layout, owners, prod_p0: int) -> jnp.ndarray:
    """Shard full per-bucket buffers back into owner rows ``[dp, m]``
    (non-owner ranks of a non-power-of-two extent get zero rows, matching
    :func:`zero1_masters_from_params`)."""
    seg_bufs = [b.reshape(prod_p0, -1) for b in bufs]
    m = layout.total_padded // prod_p0
    dtype = bufs[0].dtype if bufs else jnp.float32
    rows = [
        jnp.concatenate([sb[o] for sb in seg_bufs])
        if o is not None
        else jnp.zeros((m,), dtype)
        for o in owners
    ]
    return jnp.stack(rows)


def zero1_regrid(bufs, layout_old, layout_new) -> list:
    """Re-bucket full flat buffers from one layout to another.

    Both layouts cover the same (fp32 view of the) parameter tree; only
    the per-bucket padding differs (the pad quantum scales with the RS
    pivot product, which changes on resize).  Pad regions carry exact
    zeros throughout training — gradients, moments and EF residuals are
    all zero there by construction — so dropping the old padding and
    re-padding with zeros is bit-exact for every live coordinate.
    """
    return buckets.pack(buckets.unpack(bufs, layout_old), layout_new)


def zero1_owner_segments(mesh: Mesh, dp_axes) -> list:
    """For each flattened DP rank (axis-major order), the natural-order global
    segment index it owns after the chained RS, or None (non-pivot rank of a
    non-power-of-two axis)."""
    sizes = [mesh.shape[ax] for ax in dp_axes]
    p0s = [pivot(sz)[0] for sz in sizes]
    owners = []
    for flat_rank in range(int(np.prod(sizes))):
        idxs = list(np.unravel_index(flat_rank, sizes))
        if any(i >= q for i, q in zip(idxs, p0s)):
            owners.append(None)
        else:
            seg = 0
            for i, q in zip(idxs, p0s):
                seg = seg * q + i
            owners.append(seg)
    return owners


def make_zero1(
    cfg: ModelConfig,
    mesh: Mesh,
    tcfg: TrainConfig,
    *,
    transform: str = "identity",
    paper_mode: bool = False,
):
    """Shared builder for the bucketed flat-gradient MRD strategies.

    Params: TP-sharded (auto "model" axis), replicated across DP (manual).
    Opt state: fp32 shards owned per DP rank, global shape [dp, m] — ``m``
    concatenates the owned segment of every gradient bucket
    (``paper_mode``: every rank owns a full replica, pure RD-butterfly
    allreduce — the paper's S2 collective — and no RS/AG).  All
    gradient-scale collectives run per-bucket, pipelined stage-major
    (DESIGN.md S10).
    Global grad-norm clipping uses the paper's MRD allreduce on the scalar.

    With a lossy ``transform`` and ``tcfg.error_feedback``, each rank
    carries an EF-SGD residual (``opt['ef']``, the full padded gradient
    length): the quantization error of what it sent this step is folded
    into next step's gradient (:func:`repro.collectives.transforms.ef_roundtrip`),
    so persistently-sub-quantum coordinates are delayed, not dropped.
    """
    rules = shd.make_rules(cfg, mesh, fsdp=False)  # DP-replicated params
    remat_policy = common.REMAT_POLICIES[tcfg.remat]
    pdt = dtype_of(cfg.param_dtype)
    executor = common.resolve_executor(tcfg, compressed=transform != "identity")
    dp_axes = rules.dp_axes
    dp = rules.dp
    monitor = common.build_monitor(tcfg, rules)

    # the plan bindings: one code path for plain/compressed, 1/N axes.
    # paper_mode allreduces full buckets; the ZeRO-1 path reduce-scatters
    # them, allreduces the grad-norm scalar, and all-gathers the params.
    if paper_mode:
        full_ar = plans.allreduce_plan(
            schedule="mrd", axes=dp_axes, op="sum", transform=transform,
            executor=executor,
        )
    else:
        rs_plan = plans.reduce_scatter_plan(
            axes=dp_axes, op="sum", transform=transform, executor=executor
        )
        ag_plan = plans.allgather_plan(axes=dp_axes, executor=executor)
        scalar_ar = plans.allreduce_plan(schedule="mrd", axes=dp_axes, op="sum")

    pshape = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    layout, prod_p0 = zero1_layout(
        pshape, mesh, dp_axes, bucket_bytes=tcfg.bucket_bytes
    )
    padded = layout.total_padded
    shard_len = padded if paper_mode else padded // prod_p0
    # per-bucket split points of the concatenated shard / full vector
    full_bounds = list(np.cumsum(layout.bucket_lengths)[:-1])
    shard_bounds = [b // prod_p0 for b in full_bounds]
    use_ef = tcfg.error_feedback and transform != "identity"
    # the gradient-reducing plan: full-bucket butterfly (paper) or chained RS
    grad_plan = full_ar if paper_mode else rs_plan
    if tcfg.overlap:
        # ready-bucket overlap (DESIGN.md S16): same layout, same plan —
        # only the bucket *issue order* moves inside the backward
        koffs = overlap_lib.key_offsets(pshape)
        bgroups = overlap_lib.bucket_groups(
            layout, overlap_lib.leaf_groups(pshape)
        )

    owner_of_rank = np.asarray(
        [-1 if o is None else o for o in zero1_owner_segments(mesh, dp_axes)],
        np.int32,
    )

    def local_master_row(params):
        """This rank's ``[1, shard_len]`` slice of
        :func:`zero1_masters_from_params`, built inside the manual region so
        no device ever holds the other ranks' rows."""
        bufs = buckets.pack(
            jax.tree.map(lambda x: x.astype(jnp.float32), params), layout
        )
        if paper_mode:
            return jnp.concatenate(bufs)[None]
        rank = jnp.zeros((), jnp.int32)
        for ax in dp_axes:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        seg = jnp.asarray(owner_of_rank)[rank]
        # 1-D dynamic slices: indexing a [prod_p0, -1] reshape of each bucket
        # instead takes the TPU compiler minutes at full width
        row = jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(
                b, jnp.maximum(seg, 0) * (b.shape[0] // prod_p0),
                b.shape[0] // prod_p0,
            )
            for b in bufs
        ])
        return jnp.where(seg >= 0, row, 0.0)[None]

    def init_state(key):
        params = transformer.init_params(cfg, key)
        # jit: an eager partial-manual shard_map rejects the auto TP axis
        masters = jax.jit(jax.shard_map(
            local_master_row,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),),
            out_specs=P(dp_axes),
            axis_names=set(dp_axes),
            check_vma=False,
        ))(params)
        opt = {
            "master": masters,
            "mu": jnp.zeros((dp, shard_len), jnp.float32),
            "nu": jnp.zeros((dp, shard_len), jnp.float32),
        }
        if use_ef:
            opt["ef"] = jnp.zeros((dp, padded), jnp.float32)
        state = {
            "params": params,
            "opt": opt,
            "step": jnp.zeros((), jnp.int32),
        }
        if monitor is not None:
            state["monitor"] = common.monitor_rows_init(monitor, dp)
        return state

    def state_specs(state):
        pspecs = shd.param_specs(cfg, rules, state["params"])
        dpP = P(dp_axes)
        specs = {
            "params": pspecs,
            "opt": jax.tree.map(lambda _: dpP, state["opt"]),
            "step": P(),
        }
        if monitor is not None:
            specs["monitor"] = jax.tree.map(lambda _: dpP, state["monitor"])
        return specs

    def _is_owner():
        """Inside the manual region: does this rank own a live segment?"""
        ok = jnp.ones((), jnp.bool_)
        for ax in dp_axes:
            p0, _, _ = pivot(mesh.shape[ax])
            ok &= jax.lax.axis_index(ax) < p0
        return ok

    def train_step(state, batch):
        def local_step(params, opt, step, mon_state, local_batch):
            if use_ef:
                # EF-SGD: send the grid round-trip of (grad + residual),
                # carry what the quantizer dropped into the next step
                from repro.collectives import transforms as tf_lib

                ef_bufs = jnp.split(opt["ef"][0], full_bounds)

                def wire(i, buf):
                    return tf_lib.ef_roundtrip(buf, ef_bufs[i])

            if tcfg.overlap:
                # segmented backward feeding ready buckets straight into
                # the plan's stage pipeline (bit-identical to the
                # post-backward path below — DESIGN.md S16)
                with shd.sharding_ctx(cfg, common.manual_rules(rules)):
                    emitter = overlap_lib.segmented_grads(
                        params, local_batch, cfg, remat_policy,
                        tcfg.microbatches,
                    )
                    loss, metrics, red, efs = overlap_lib.drive(
                        emitter, layout, koffs, bgroups,
                        plan=grad_plan, wire=wire if use_ef else None,
                    )
                if use_ef:
                    new_ef = jnp.concatenate(efs)
            else:
                with shd.sharding_ctx(cfg, common.manual_rules(rules)):
                    grads, loss, metrics = common.microbatched_grads(
                        params, local_batch, cfg, remat_policy, tcfg.microbatches
                    )
                # dtype-homogeneous, quantum-padded gradient buckets
                bufs = buckets.pack(
                    jax.tree.map(lambda g: g.astype(jnp.float32), grads), layout
                )
                if use_ef:
                    pairs = [wire(i, b) for i, b in enumerate(bufs)]
                    bufs = [s for s, _ in pairs]
                    new_ef = jnp.concatenate([e for _, e in pairs])
                # paper_mode: the paper's Allreduce, a full-buffer XOR
                # butterfly per DP axis; else the beyond-paper chained RS —
                # either way one pipelined stage-major pass over all buckets
                red = grad_plan.run_buffers(bufs)
            if paper_mode:
                gshard = jnp.concatenate(red) / dp
                gnorm = jnp.sqrt(jnp.sum(gshard * gshard))
            else:
                # concatenated mean segments of the reduce-scattered buckets
                gshard = jnp.concatenate(red) / dp
                # global grad norm via the paper's MRD allreduce on a scalar
                own = _is_owner()
                sq = jnp.where(own, jnp.sum(gshard * gshard), 0.0)
                gnorm = jnp.sqrt(scalar_ar.run(sq))
            if tcfg.optimizer.grad_clip > 0:
                scale = jnp.minimum(
                    1.0, tcfg.optimizer.grad_clip / jnp.maximum(gnorm, 1e-12)
                )
                gshard = gshard * scale
            master, new_opt = opt_lib.apply_update_vector(
                gshard,
                {"master": opt["master"][0], "mu": opt["mu"][0], "nu": opt["nu"][0]},
                tcfg.optimizer,
                step,
            )
            if paper_mode:
                out_bufs = jnp.split(master.astype(pdt), full_bounds)
            else:
                # recursive-doubling all-gather of the updated bf16 params,
                # again pipelined per bucket
                out_bufs = ag_plan.run_buffers(
                    jnp.split(master.astype(pdt), shard_bounds)
                )
            # unpack casts each bucket back to its layout dtype (fp32)
            new_params = buckets.unpack(out_bufs, layout)
            new_params = jax.tree.map(
                lambda a, b: a.astype(b.dtype), new_params, params
            )

            mon_out, done, val = common.local_monitor_tick(
                monitor, mon_state, metrics["per_example"].mean(), step
            )
            opt_out = jax.tree.map(lambda x: x[None], new_opt)
            if use_ef:
                opt_out["ef"] = new_ef[None]
            return (
                new_params,
                opt_out,
                mon_out,
                loss[None],
                gnorm[None],
                done,
                val,
            )

        dpP = P(dp_axes)
        opt_spec = jax.tree.map(lambda _: dpP, state["opt"])
        bspecs = common.batch_specs(cfg, rules, batch)
        if monitor is not None:
            mon_state_in = state["monitor"]
            mon_spec = jax.tree.map(lambda _: dpP, state["monitor"])
        else:
            mon_state_in = jnp.zeros((dp, 1), jnp.float32)
            mon_spec = dpP
        out = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(), state["params"]),
                opt_spec,
                P(),
                mon_spec,
                bspecs,
            ),
            out_specs=(
                jax.tree.map(lambda _: P(), state["params"]),
                opt_spec,
                mon_spec,
                dpP,
                dpP,
                dpP,
                dpP,
            ),
            axis_names=set(dp_axes),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], mon_state_in, batch)
        params, opt, mon, loss, gnorm, done, val = out
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        if monitor is not None:
            new_state["monitor"] = mon
        metrics = {
            "loss": loss.mean(),
            "grad_norm": gnorm[0],
            "converged": done[0],
            "monitor_value": val[0],
        }
        return new_state, metrics

    return train_step, init_state, state_specs, rules


@register("mrd_zero1")
def make(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    return make_zero1(cfg, mesh, tcfg)


# ---------------------------------------------------------------------------
# Elastic resize (DESIGN.md S12): in-place ZeRO-1 shard re-layout
# ---------------------------------------------------------------------------


def resize_zero1(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    old_mesh: Mesh,
    new_mesh: Mesh,
    state,
    keep,
    *,
    paper_mode: bool = False,
):
    """Migrate a live zero1/paper/compressed train state across a resize
    without a checkpoint round-trip.

    Params are DP-replicated (any survivor holds them — the controller
    broadcasts to joiners over an MRD plan at the new extent).  The fp32
    master/moment rows are owner-segment sharded over the *old* pivot
    product; we reassemble the full flat vectors from the surviving
    owners, re-bucket for the new extent's layout (:func:`zero1_regrid` —
    bit-exact, pad regions are structurally zero), and re-scatter onto
    the new owner segments.  The EF-SGD residual is per-worker state and
    follows its worker via ``keep`` (joiners start with a zero residual —
    they have sent nothing to compensate for).  Monitor rows migrate via
    the detection-protocol layer.
    """
    rules_o = shd.make_rules(cfg, old_mesh, fsdp=False)
    rules_n = shd.make_rules(cfg, new_mesh, fsdp=False)
    pshape = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    layout_o, prod_o = zero1_layout(
        pshape, old_mesh, rules_o.dp_axes, bucket_bytes=tcfg.bucket_bytes
    )
    layout_n, prod_n = zero1_layout(
        pshape, new_mesh, rules_n.dp_axes, bucket_bytes=tcfg.bucket_bytes
    )
    owners_o = zero1_owner_segments(old_mesh, rules_o.dp_axes)
    owners_n = zero1_owner_segments(new_mesh, rules_n.dp_axes)
    bounds_o = list(np.cumsum(layout_o.bucket_lengths)[:-1])
    dp_n = rules_n.dp

    opt = state["opt"]
    new_opt = {}
    for name in ("master", "mu", "nu"):
        rows = jnp.asarray(opt[name])
        if paper_mode:
            # fully replicated rows: re-bucket one survivor's copy
            full = zero1_regrid(
                jnp.split(rows[0], bounds_o), layout_o, layout_n
            )
            flat = jnp.concatenate(full)
            new_opt[name] = jnp.broadcast_to(flat, (dp_n, flat.shape[0]))
        else:
            bufs = zero1_gather_buckets(rows, layout_o, owners_o, prod_o)
            bufs = zero1_regrid(bufs, layout_o, layout_n)
            new_opt[name] = zero1_scatter_buckets(bufs, layout_n, owners_n, prod_n)
    if "ef" in opt:
        ef_rows = jnp.asarray(opt["ef"])
        zero_row = None
        rows_out = []
        for k in keep:
            if k is None:
                if zero_row is None:
                    zero_row = jnp.zeros((layout_n.total_padded,), jnp.float32)
                rows_out.append(zero_row)
            else:
                regridded = zero1_regrid(
                    jnp.split(ef_rows[int(k)], bounds_o), layout_o, layout_n
                )
                rows_out.append(jnp.concatenate(regridded))
        new_opt["ef"] = jnp.stack(rows_out)

    new_state = dict(state)
    new_state["opt"] = new_opt
    if "monitor" in state:
        new_state["monitor"] = common.monitor_rows_migrate(
            tcfg, rules_n, state["monitor"], keep
        )
    return new_state


@register_resize("mrd_zero1")
def _resize(cfg, tcfg, old_mesh, new_mesh, state, keep):
    return resize_zero1(cfg, tcfg, old_mesh, new_mesh, state, keep)


@register_resize("mrd_paper")
def _resize_paper(cfg, tcfg, old_mesh, new_mesh, state, keep):
    return resize_zero1(
        cfg, tcfg, old_mesh, new_mesh, state, keep, paper_mode=True
    )


@register_resize("compressed")
def _resize_compressed(cfg, tcfg, old_mesh, new_mesh, state, keep):
    return resize_zero1(cfg, tcfg, old_mesh, new_mesh, state, keep)
