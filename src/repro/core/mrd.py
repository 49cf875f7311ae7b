"""Deprecated shim: MRD executors moved into the layered collectives
subsystem (``repro.collectives``).  Every public name keeps working, but
each function is now a thin wrapper over a :class:`CollectivePlan`, so
blocking/non-blocking, compressed/plain, device/sim all execute through
the single validated stage interpreter (``repro.collectives.plans``).

New code should build plans directly::

    from repro.collectives import allreduce_plan, reduce_scatter_plan
    plan = allreduce_plan(schedule="mrd", axes=("data",), op="sum")
    out = plan.run(tree)                       # inside shard_map
    rs = reduce_scatter_plan(axes=("data",), transform="int8").run(vec)
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.collectives import plans
from repro.collectives.executors import (  # noqa: F401
    OPS,
    DeviceBackend,
    SimBackend,
    resolve_op as _resolve_op,
)
from repro.collectives.plans import exec_stage
from repro.collectives.schedules import (  # noqa: F401
    Stage,
    allgather_schedule,
    allreduce_schedule,
    pivot,
    rabenseifner_schedule,
    reduce_scatter_schedule,
)


def _exec_allreduce_stage(x, st: Stage, be, p: int, op: Callable):
    """Back-compat alias for the plan layer's stage interpreter."""
    return exec_stage(x, st, be, p, op)


# ---------------------------------------------------------------------------
# Device API (call inside shard_map over `axis_name`)
# ---------------------------------------------------------------------------


def axis_size(axis_name: str) -> int:
    return jax.lax.axis_size(axis_name)


def allreduce(tree, axis_name: str, *, op: str | Callable = "sum"):
    """Paper-faithful MRD allreduce of a pytree over ``axis_name``.

    Latency-optimal: log2(p0)+2 stages, full payload each stage.
    """
    return plans.allreduce_plan(schedule="mrd", axes=(axis_name,), op=op).run(tree)


def reduce_scatter(vec, axis_name: str, *, op: str | Callable = "sum"):
    """Recursive-halving reduce-scatter of a 1-D vector. ``len(vec)`` must be
    divisible by p0; ranks >= p0 return junk (mask or ignore)."""
    p = axis_size(axis_name)
    if p == 1:
        return vec
    p0, _, _ = pivot(p)
    if vec.ndim != 1 or vec.shape[0] % p0:
        raise ValueError(f"need 1-D vec with len % {p0} == 0, got {vec.shape}")
    return plans.reduce_scatter_plan(axes=(axis_name,), op=op).run(vec)


def compressed_reduce_scatter(vec, axis_name: str, *, block: int = 256):
    """Reduce-scatter with int8-quantized wire payloads (beyond-paper).

    Each recursive-halving stage quantizes the outgoing half blockwise and
    dequant-accumulates on receive (the ``mrd_combine`` kernel's op).  Wire
    bytes drop ~4x vs fp32.  Quantization noise is bounded per stage
    (|err| <= amax/254 per block) but uncompensated (no error feedback yet).
    """
    p = axis_size(axis_name)
    if p == 1:
        return vec
    p0, _, _ = pivot(p)
    if vec.ndim != 1 or vec.shape[0] % (p0 * block):
        raise ValueError(f"need len % {p0 * block} == 0, got {vec.shape}")
    return plans.reduce_scatter_plan(
        axes=(axis_name,), transform="int8", block=block
    ).run(vec)


def allgather(seg, axis_name: str):
    """Recursive-doubling all-gather of each pivot rank's 1-D segment."""
    if axis_size(axis_name) == 1:
        return seg
    return plans.allgather_plan(axes=(axis_name,)).run(seg)


def rabenseifner_allreduce(vec, axis_name: str, *, op: str | Callable = "sum"):
    """Bandwidth-optimal allreduce (beyond-paper; paper ref. [20]):
    reduce-scatter + all-gather, ~2n per rank instead of n*log2(p0)."""
    return plans.allreduce_plan(
        schedule="rabenseifner", axes=(axis_name,), op=op
    ).run(vec)


def hierarchical_allreduce(
    vec, inner_axis: str, outer_axis: str, *, op: str | Callable = "sum"
):
    """Pod-aware allreduce (beyond-paper): reduce-scatter within ``inner_axis``
    (intra-pod ICI), MRD allreduce across ``outer_axis`` (inter-pod DCN) on the
    1/p0_inner-size shard, then all-gather within ``inner_axis``.

    Inter-pod traffic drops from n*log2(pods) to (n/p0_inner)*log2(pods)."""
    return plans.allreduce_plan(
        schedule="hierarchical", axes=(inner_axis, outer_axis), op=op
    ).run(vec)


def tree_allreduce_flat(
    tree,
    axis_name: str,
    *,
    op: str | Callable = "sum",
    schedule: str = "rabenseifner",
    bucket_bytes=None,
):
    """Allreduce a pytree through the bucketed engine (DESIGN.md S10).

    ``schedule``: any registered schedule name; 'mrd' (paper),
    'rabenseifner' (beyond-paper, default for bandwidth-bound payloads
    like gradients).  ``bucket_bytes`` caps each dtype-homogeneous wire
    bucket (None = one bucket per dtype — the closest analog of the
    historical flat-ravel path, but dtype-preserving).
    """
    if axis_size(axis_name) == 1:
        return tree
    return plans.tree_allreduce(
        tree, schedule=schedule, op=op, axes=(axis_name,),
        bucket_bytes=bucket_bytes,
    )


# ---------------------------------------------------------------------------
# Simulation API (single device, stacked rank axis)
# ---------------------------------------------------------------------------


def sim_allreduce(x, *, op: str | Callable = "sum"):
    """x: [p, ...] stacked per-rank values -> [p, ...] (all rows = reduction)."""
    p = x.shape[0]
    if p == 1:
        return x
    return plans.allreduce_plan(schedule="mrd", p=p, op=op).run(x)


def sim_reduce_scatter(x, *, op: str | Callable = "sum"):
    """x: [p, n] with n % p0 == 0 -> [p, n/p0] (rows >= p0 are junk)."""
    p = x.shape[0]
    if p == 1:
        return x
    p0, _, _ = pivot(p)
    if x.shape[1] % p0:
        raise ValueError(f"n={x.shape[1]} not divisible by p0={p0}")
    return plans.reduce_scatter_plan(p=p, op=op).run(x)


def sim_allgather(x):
    """x: [p, m] segments (rows >= p0 junk) -> [p, m*p0]."""
    p = x.shape[0]
    if p == 1:
        return x
    return plans.allgather_plan(p=p).run(x)


def sim_rabenseifner_allreduce(x, *, op: str | Callable = "sum"):
    p = x.shape[0]
    if p == 1:
        return x
    return plans.allreduce_plan(schedule="rabenseifner", p=p, op=op).run(x)


# ---------------------------------------------------------------------------
# Whole-array convenience wrappers (build the shard_map for the caller)
# ---------------------------------------------------------------------------


def make_allreduce(mesh, axis_name: str, *, op: str = "sum", schedule: str = "mrd"):
    """Returns a jitted fn: [p, ...] global array sharded over ``axis_name`` ->
    allreduced array of the same shape (each shard = full reduction)."""
    from jax.sharding import PartitionSpec as P

    spec = P(axis_name)

    def fn(x):
        def local(v):
            y = v[0]
            if schedule == "psum":
                out = jax.lax.psum(y, axis_name)
            elif schedule == "mrd":
                out = allreduce(y, axis_name, op=op)
            else:
                plan = plans.allreduce_plan(
                    schedule=schedule, axes=(axis_name,), op=op
                )
                flat = y.reshape(-1)
                pad = (-flat.shape[0]) % plan.pad_quantum()
                out = plan.run(jnp.pad(flat, (0, pad)))
                out = out[: flat.shape[0]].reshape(y.shape)
            return out[None]

        return jax.shard_map(
            local, mesh=mesh, in_specs=spec, out_specs=spec,
            axis_names={axis_name}, check_vma=False,
        )(x)

    return jax.jit(fn)
