"""Layer 3 of the asynchrony subsystem: *detection protocols*
(``DETECTION_PROTOCOLS``).

A protocol is an ``init / tick / finalize`` object layered over a
:class:`repro.collectives.plans.CollectivePlan` (sim executor in the engine,
device executor in the training-loop :class:`ConvergenceMonitor` — both are
built from this registry, so sim and device training share protocol code):

- ``init(p, m, cfg)``: the protocol's carried state pytree; always contains
  ``res_norm`` (the certified value, latched at :data:`RES_INIT`) and
  ``detected``.
- ``tick(state, obs)``: advance one engine tick; returns ``(state,
  coll_msgs)`` where ``coll_msgs`` is this tick's collective message count
  (paper S2 accounting).  ``obs`` is an :class:`Obs` snapshot of the
  engine's tick.
- ``finalize(state, x)``: the solution the protocol certifies (``x̄`` for
  the snapshot-exact protocol, the live iterate otherwise) — vmappable, so
  :func:`repro.asynchrony.engine.sweep` can finalize whole batches.

Registered protocols: ``inexact`` (paper Alg. 1), ``exact`` (paper Alg. 2,
Chandy–Lamport snapshot), ``oracle`` (physically unrealizable ground truth),
``sync`` (classic synchronous iteration + blocking allreduce; the engine
reads ``synchronous=True`` and pins full activity / zero delays), and
``interval`` (Alg. 1 hardened: each worker contributes the *max over a
sliding window* of its update magnitudes, so a single momentarily-small
update cannot certify — the window default covers the staleness bound).

Protocols that support the training loop also define ``monitor_init`` /
``monitor_contribution`` — the per-step latching policy the
:class:`ConvergenceMonitor` composes with a device plan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.collectives import plans
from repro.core import snapshot

# Public finite 'infinity' for residual latches (was detection._BIG).
RES_INIT = 1e30


@dataclasses.dataclass(frozen=True)
class Obs:
    """One engine tick as seen by a protocol (all leaves traced)."""

    x: Any  # [p, m] current blocks
    update_mag: Any  # [p] last local update magnitude per worker
    tick: Any  # scalar int32
    key: Any  # per-tick PRNG key (snapshot marker delays)
    fp: Any  # the FixedPoint being solved (static)
    eps: float
    max_delay: int
    msg_table: Any  # [S] messages sent at MRD stage s
    coll_cycle_msgs: Any  # messages of one full blocking cycle


def _sim_plan(p: int) -> plans.CollectivePlan:
    return plans.allreduce_plan(schedule="mrd", p=p, op="max")


def _take_ranks(arr, keep, fill, axis: int = 0):
    """Select worker rows along ``axis`` per the resize ``keep`` map.

    ``keep[i]`` is the old rank now at new rank ``i`` (None = a joined
    worker, which gets ``fill``).  Works for any rank-axis position —
    ``res_loc [p]``, ``win [W, p]``, stacked monitor rows ``[dp, ...]``.
    """
    parts = []
    for k in keep:
        if k is None:
            parts.append(jnp.full_like(jnp.take(arr, 0, axis=axis), fill))
        else:
            parts.append(jnp.take(arr, int(k), axis=axis))
    return jnp.stack(parts, axis=axis)


def _stage_msgs(msg_table, stage):
    return msg_table[jnp.minimum(stage, msg_table.shape[0] - 1)]


DETECTION_PROTOCOLS: Dict[str, Any] = {}


def register_protocol(name: str):
    def deco(cls):
        DETECTION_PROTOCOLS[name] = cls()
        return cls

    return deco


def get_protocol(name: str):
    try:
        return DETECTION_PROTOCOLS[name]
    except KeyError:
        raise ValueError(
            f"unknown detection protocol {name!r}; "
            f"registered: {sorted(DETECTION_PROTOCOLS)}"
        ) from None


class _ProtocolBase:
    """Default surfaces shared by the registered protocols."""

    synchronous = False

    def finalize(self, state, x):
        """Solution to report at termination (default: the live iterate)."""
        return x.reshape(x.shape[:-2] + (-1,)) if x.ndim > 2 else x.reshape(-1)

    # -- elastic resize (DESIGN.md S12) --------------------------------------

    def migrate(self, state, keep, new_p: int, m: int, cfg):
        """Re-lay-out protocol state after the worker set changes.

        ``keep[i]`` = old rank now at new rank ``i`` (None = joined).
        The in-flight non-blocking reduction is abandoned — its stage
        counter and partial combines are meaningless at the new extent,
        and the MRD plan at ``new_p`` has a different cycle length — while
        everything certified so far (``res_norm``, ``detected``) and
        per-worker latches survive.  Subclasses extend this for their
        extra per-worker state.
        """
        new = self.init(new_p, m, cfg)
        for k_ in ("res_norm", "detected"):
            if k_ in new and k_ in state:
                new[k_] = state[k_]
        return new

    # -- training-loop policy (optional) ------------------------------------

    def monitor_init(self, metric0):
        raise NotImplementedError(
            f"protocol {type(self).__name__} has no training-loop policy"
        )

    def monitor_contribution(self, mstate, metric, step_idx, cycle_length):
        raise NotImplementedError


@register_protocol("inexact")
@dataclasses.dataclass(frozen=True)
class InexactProtocol(_ProtocolBase):
    """Paper Algorithm 1: non-blocking Allreduce of local update magnitudes.

    Each cycle re-latches the worker's *current* ``res_loc``; contributions
    mix different local iterations (hence inexact), but the detector never
    blocks an iteration.
    """

    name: str = "inexact"

    def init(self, p: int, m: int, cfg):
        return {
            "nb": _sim_plan(p).init(jnp.full((p,), RES_INIT, jnp.float32)),
            "res_loc": jnp.full((p,), RES_INIT, jnp.float32),
            "res_norm": jnp.full((), RES_INIT, jnp.float32),
            "detected": jnp.zeros((), jnp.bool_),
        }

    def tick(self, st, obs: Obs):
        p = obs.update_mag.shape[0]
        msgs = _stage_msgs(obs.msg_table, st["nb"]["stage"])
        nb = _sim_plan(p).step(st["nb"], st["res_loc"])
        flag = nb["flag"]
        res_norm = jnp.where(flag, jnp.max(nb["result"]), st["res_norm"])
        res_loc = jnp.where(flag, obs.update_mag, st["res_loc"])
        detected = st["detected"] | (flag & (res_norm < obs.eps))
        return {
            "nb": nb, "res_loc": res_loc,
            "res_norm": res_norm, "detected": detected,
        }, msgs

    def migrate(self, state, keep, new_p, m, cfg):
        new = super().migrate(state, keep, new_p, m, cfg)
        # surviving workers re-latch their last contribution on the next
        # cycle start; joiners start at the RES_INIT sentinel
        new["res_loc"] = _take_ranks(state["res_loc"], keep, RES_INIT)
        return new

    def monitor_init(self, metric0):
        return {}

    def monitor_contribution(self, mstate, metric, step_idx, cycle_length):
        return mstate, metric


@register_protocol("exact")
@dataclasses.dataclass(frozen=True)
class ExactProtocol(_ProtocolBase):
    """Paper Algorithm 2: Chandy–Lamport snapshot -> residual on the frozen
    x̄ -> non-blocking Allreduce.  Certification is exact for the returned
    x̄; a failed certification starts a new snapshot."""

    name: str = "exact"

    def init(self, p: int, m: int, cfg):
        return {
            "snap": snapshot.init(p, m),
            "nb": _sim_plan(p).init(jnp.full((p,), RES_INIT, jnp.float32)),
            "res_loc": jnp.full((p,), RES_INIT, jnp.float32),
            "res_norm": jnp.full((), RES_INIT, jnp.float32),
            "mode": jnp.zeros((), jnp.int32),  # 0 = snapshot, 1 = reduce
            "xbar": jnp.zeros((p * m,), jnp.float32),
            "detected": jnp.zeros((), jnp.bool_),
        }

    def tick(self, st, obs: Obs):
        p, m = obs.x.shape

        def snapshot_phase(d):
            snap = d["snap"]
            fresh = ~snap["in_progress"]
            started = snapshot.start(snap, obs.tick, obs.key, obs.max_delay)
            snap = jax.tree.map(lambda a, b: jnp.where(fresh, a, b), started, snap)
            snap = snapshot.tick(snap, obs.x, obs.tick)
            fin = snapshot.done(snap, obs.tick)
            xbar = snapshot.assembled(snap)
            fx = obs.fp.full_map(xbar)
            res_blocks = jnp.max(jnp.abs(fx - xbar).reshape(p, m), axis=1)
            return {
                **d,
                "snap": {**snap, "in_progress": snap["in_progress"] & ~fin},
                "res_loc": jnp.where(fin, res_blocks, d["res_loc"]),
                "xbar": jnp.where(fin, xbar, d["xbar"]),
                "mode": jnp.where(fin, 1, d["mode"]),
            }

        def reduce_phase(d):
            nb = _sim_plan(p).step(d["nb"], d["res_loc"])
            flag = nb["flag"]
            res_norm = jnp.where(flag, jnp.max(nb["result"]), d["res_norm"])
            det_now = flag & (res_norm < obs.eps)
            return {
                **d,
                "nb": nb,
                "res_norm": res_norm,
                "detected": d["detected"] | det_now,
                "mode": jnp.where(flag & ~det_now, 0, d["mode"]),
            }

        in_reduce = st["mode"] == 1
        # snapshot markers + data replies (all-to-all) when a snapshot starts
        started = (~in_reduce) & ~st["snap"]["in_progress"]
        msgs = jnp.where(
            in_reduce, _stage_msgs(obs.msg_table, st["nb"]["stage"]), 0
        ) + jnp.where(started, 2 * p * (p - 1), 0)
        new = jax.lax.cond(in_reduce, reduce_phase, snapshot_phase, st)
        return new, msgs

    def finalize(self, state, x):
        return state["xbar"]

    def migrate(self, state, keep, new_p, m, cfg):
        new = super().migrate(state, keep, new_p, m, cfg)
        # an in-progress snapshot is a cut of the *old* worker set —
        # discard it (a fresh one starts next tick); the last certified
        # x̄ carries over when the global problem size is unchanged
        if state["xbar"].shape == new["xbar"].shape:
            new["xbar"] = state["xbar"]
        return new

    def monitor_init(self, metric0):
        return {"latched": metric0}

    def monitor_contribution(self, mstate, metric, step_idx, cycle_length):
        latch_now = (step_idx % cycle_length) == 0
        latched = jnp.where(latch_now, metric, mstate["latched"])
        return {"latched": latched}, latched


@register_protocol("interval")
@dataclasses.dataclass(frozen=True)
class IntervalProtocol(_ProtocolBase):
    """Windowed Algorithm 1: each worker's contribution is the max of its
    update magnitudes over the last ``window`` ticks, so certification means
    updates stayed below eps across a whole window (default
    ``max_delay + 2`` — covering the staleness bound), not at one instant.
    Same message cost as ``inexact``."""

    name: str = "interval"

    def _window(self, cfg) -> int:
        w = getattr(cfg, "window", 0)
        return int(w) if w else int(cfg.max_delay) + 2

    def init(self, p: int, m: int, cfg):
        W = self._window(cfg)
        return {
            "nb": _sim_plan(p).init(jnp.full((p,), RES_INIT, jnp.float32)),
            "win": jnp.full((W, p), RES_INIT, jnp.float32),
            "res_loc": jnp.full((p,), RES_INIT, jnp.float32),
            "res_norm": jnp.full((), RES_INIT, jnp.float32),
            "detected": jnp.zeros((), jnp.bool_),
        }

    def tick(self, st, obs: Obs):
        p = obs.update_mag.shape[0]
        W = st["win"].shape[0]
        win = st["win"].at[jnp.mod(obs.tick, W)].set(obs.update_mag)
        msgs = _stage_msgs(obs.msg_table, st["nb"]["stage"])
        nb = _sim_plan(p).step(st["nb"], st["res_loc"])
        flag = nb["flag"]
        res_norm = jnp.where(flag, jnp.max(nb["result"]), st["res_norm"])
        res_loc = jnp.where(flag, jnp.max(win, axis=0), st["res_loc"])
        detected = st["detected"] | (flag & (res_norm < obs.eps))
        return {
            "nb": nb, "win": win, "res_loc": res_loc,
            "res_norm": res_norm, "detected": detected,
        }, msgs

    def migrate(self, state, keep, new_p, m, cfg):
        new = super().migrate(state, keep, new_p, m, cfg)
        new["res_loc"] = _take_ranks(state["res_loc"], keep, RES_INIT)
        # per-worker window columns follow their workers; joiners start
        # saturated so they cannot certify before filling a whole window
        new["win"] = _take_ranks(state["win"], keep, RES_INIT, axis=1)
        return new

    def monitor_init(self, metric0, window: int = 8):
        return {"win": jnp.broadcast_to(metric0, (window,)).astype(jnp.float32)}

    def monitor_contribution(self, mstate, metric, step_idx, cycle_length):
        win = mstate["win"]
        win = win.at[jnp.mod(step_idx, win.shape[0])].set(metric)
        return {"win": win}, jnp.max(win)


@register_protocol("oracle")
@dataclasses.dataclass(frozen=True)
class OracleProtocol(_ProtocolBase):
    """Ground truth (physically unrealizable): the true residual of the
    *current* global iterate, free of charge.  The baseline every realizable
    protocol's detection delay is measured against."""

    name: str = "oracle"

    def init(self, p: int, m: int, cfg):
        return {
            "res_norm": jnp.full((), RES_INIT, jnp.float32),
            "detected": jnp.zeros((), jnp.bool_),
        }

    def tick(self, st, obs: Obs):
        res = obs.fp.residual_norm(obs.x.reshape(-1))
        return {"res_norm": res, "detected": res < obs.eps}, jnp.zeros((), jnp.int32)


@register_protocol("sync")
@dataclasses.dataclass(frozen=True)
class SyncProtocol(_ProtocolBase):
    """Classic synchronous iteration: full activity, zero delays (the engine
    honors ``synchronous``), blocking Allreduce of update magnitudes every
    iteration — the paper's Fig. 5 comparison arm."""

    name: str = "sync"
    synchronous = True

    def init(self, p: int, m: int, cfg):
        return {
            "res_norm": jnp.full((), RES_INIT, jnp.float32),
            "detected": jnp.zeros((), jnp.bool_),
        }

    def tick(self, st, obs: Obs):
        res = jnp.max(obs.update_mag)
        return {"res_norm": res, "detected": res < obs.eps}, obs.coll_cycle_msgs


# ---------------------------------------------------------------------------
# Training-loop monitor (device executor) — built from the same registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConvergenceMonitor:
    """Paper's detection embedded in a training step, over the DP mesh axes.

    ``mode`` names any :data:`DETECTION_PROTOCOLS` entry with a
    training-loop policy (``inexact``, ``exact``, ``interval``); the policy
    decides what each rank contributes per step, and the reduction itself is
    the same staged MRD plan the sim engine drives — one scalar ppermute per
    step, never blocking.

    ``mode='inexact'``: each cycle latches the worker's *current* metric
    (e.g. local grad-norm or loss delta); the certified global value lags by
    ``cycle_length`` steps and may mix step indices across workers — exactly
    the paper's Algorithm 1 trade-off.

    ``mode='exact'``: contributions are latched only from steps where
    ``step_idx % cycle_length == 0``; all workers therefore reduce metrics
    from the *same* global step (a consistent cut — the BSP analogue of the
    snapshot), so the certified value is exact for that step.

    ``mode='interval'``: each rank contributes the max of its last
    ``window`` metrics, certifying a whole window of small values.

    ``axis_name`` may be a single mesh axis or a tuple (e.g. a multi-pod
    ``("pod", "data")`` DP domain): the underlying plan chains the per-axis
    MRD schedules into one stage list, so detection over a product of axes
    costs one scalar ppermute per step exactly like the single-axis case.

    Use inside shard_map/jit: ``state, done, value = monitor.step(state,
    metric, step_idx)``.
    """

    axis_name: Any  # str or tuple of axis names (e.g. ("pod","data"))
    threshold: float
    mode: str = "inexact"  # any DETECTION_PROTOCOLS entry with a monitor policy
    op: str = "max"
    window: int = 8  # 'interval' mode: metrics per certified window

    def _axes(self) -> tuple[str, ...]:
        if isinstance(self.axis_name, str):
            return (self.axis_name,)
        return tuple(self.axis_name)

    def _plan(self) -> plans.CollectivePlan:
        return plans.allreduce_plan(schedule="mrd", axes=self._axes(), op=self.op)

    def _protocol(self):
        proto = get_protocol(self.mode)
        if type(proto).monitor_init is _ProtocolBase.monitor_init:
            raise ValueError(
                f"protocol {self.mode!r} has no training-loop policy; "
                "use one of "
                + str(sorted(
                    n for n, pr in DETECTION_PROTOCOLS.items()
                    if type(pr).monitor_init is not _ProtocolBase.monitor_init
                ))
            )
        return proto

    def _monitor_init(self, proto, metric0):
        if self.mode == "interval":
            return proto.monitor_init(metric0, window=self.window)
        return proto.monitor_init(metric0)

    def init(self, varying: bool = True) -> dict[str, Any]:
        """``varying=True`` when called *inside* a shard_map region with VMA
        checking on (marks state as varying over the manual axes so it can be
        carried through scan/while).  Use ``varying=False`` when building the
        global state outside shard_map (e.g. replicated-then-sharded train
        state)."""
        proto = self._protocol()
        metric0 = jnp.full((), RES_INIT, jnp.float32)
        state = {
            "nb": plans.allreduce_plan(schedule="mrd", p=1).init(metric0),
            "m": self._monitor_init(proto, metric0),
            "value": metric0,
            "done": jnp.zeros((), jnp.bool_),
        }
        if not varying:
            return state
        return jax.tree.map(
            lambda x: jax.lax.pcast(x, self._axes(), to="varying"), state
        )

    def migrate_rows(self, rows, keep):
        """Elastic resize of replicated-then-sharded monitor state.

        ``rows`` is the ``[dp, ...]``-leaved pytree built by
        ``monitor_rows_init``; ``keep[i]`` is the old DP rank now at new
        rank ``i`` (None = joined worker, which gets a fresh row).  The
        per-rank policy state (``m`` — the exact-mode latch, the interval
        window), the certified ``value`` and the ``done`` latch follow
        their workers; the staged non-blocking reduction restarts from
        stage 0 because the MRD cycle length at the new extent differs
        and a mid-cycle partial combine would mix extents.
        """
        fresh = self.init(varying=False)

        def sel(rows_leaf, fresh_leaf):
            parts = [
                rows_leaf[k] if k is not None else fresh_leaf for k in keep
            ]
            return jnp.stack([jnp.asarray(x) for x in parts])

        migrated = jax.tree.map(sel, rows, fresh)
        migrated["nb"] = jax.tree.map(
            lambda f: jnp.broadcast_to(f, (len(keep),) + f.shape),
            fresh["nb"],
        )
        return migrated

    def step(self, state, local_metric, step_idx):
        local_metric = local_metric.astype(jnp.float32)
        proto = self._protocol()
        plan = self._plan()
        mstate, contribution = proto.monitor_contribution(
            state["m"], local_metric, step_idx, plan.cycle_length()
        )
        nb = plan.step(state["nb"], contribution)
        value = jnp.where(nb["flag"], nb["result"], state["value"])
        done = state["done"] | (nb["flag"] & (value < self.threshold))
        return (
            {"nb": nb, "m": mstate, "value": value, "done": done},
            done,
            value,
        )
