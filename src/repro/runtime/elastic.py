"""Elastic training runtime: policy-driven shrink *and* grow with in-place
state migration (DESIGN.md S12).

The paper's modified recursive doubling makes every collective correct at
*any* process count, which is exactly what makes live elasticity cheap:
losing or admitting a worker changes the DP extent to an arbitrary —
usually non-power-of-two — value and the MRD plan layer keeps working.
This module turns that property into a runtime:

- an ``ELASTIC_POLICIES`` registry (``repro.runtime.policies``) decides
  per step whether to shrink (heartbeat failure, straggler drain), grow
  (pending join), abort (``static``), or keep training;
- a :class:`ResizeEvent` lifecycle executes the decision **without a
  checkpoint round-trip** when the survivors hold the data: the mesh is
  rebuilt (:func:`~repro.runtime.fault_tolerance.shrink_mesh` /
  :func:`~repro.runtime.fault_tolerance.grow_mesh`), live collective
  plans are invalidated (``repro.collectives.plans.invalidate_all_plans``),
  and the grad-sync strategy's registered resize hook
  (``repro.distributed.gradsync.migrate_state``) re-lays-out whatever it
  shards over DP — the ZeRO-1 master/moment segments, the EF-SGD residual
  carry, the detection-protocol monitor rows — onto the new extent;
- on grow, joiners receive the parameters through an MRD-plan *broadcast*
  at the new extent (:func:`mrd_broadcast`): the sum-allreduce of a
  source-masked tree is bit-exact (every other contribution is a true
  zero), so a 3→5 grow resumes with the survivors' params untouched.

Failure detection runs on the injected clock of
:class:`~repro.runtime.fault_tolerance.FailureDetector`; the chaos
harness (``tests/chaos.py``) scripts kill/join/stall events against a
:class:`~repro.runtime.fault_tolerance.StepClock`, which makes every
resize — and therefore the whole training trajectory — a deterministic
function of the event script.  The checkpointer remains the fallback for
the data-loss case (and for cold starts); ``ElasticTrainer.restores``
counts how often it was actually needed.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.checkpoint.checkpointer import Checkpointer
from repro.collectives import plans
from repro.runtime.fault_tolerance import (
    FailureDetector,
    HeartbeatConfig,
    ReplicaSet,
    StepClock,
    grow_mesh,
    shrink_mesh,
)
from repro.runtime.policies import (
    LoadSnapshot,
    ResizeDecision,
    clamp_min_extent,
    get_policy,
)


def _dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def mrd_broadcast(tree, mesh, dp_axes: Sequence[str], src: int = 0,
                  executor: str = "device"):
    """Broadcast ``tree`` from flattened-DP rank ``src`` to every rank via
    the paper's MRD sum-allreduce at the mesh's (possibly non-power-of-two)
    extent: every non-source rank contributes exact zeros, and ``x + 0``
    is bit-exact in every stage of the schedule, so the result equals the
    source's values on all ranks.  This is the grow path's param transfer —
    the protocol-level move a joining worker performs instead of a
    checkpoint restore."""
    plan = plans.allreduce_plan(
        schedule="mrd", axes=tuple(dp_axes), op="sum", executor=executor
    )

    def local(t):
        r = jnp.zeros((), jnp.int32)
        for ax in dp_axes:
            r = r * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        masked = jax.tree.map(
            lambda x: jnp.where(r == src, x, jnp.zeros_like(x)), t
        )
        return plan.run(masked)

    rep = jax.tree.map(lambda _: P(), tree)
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=(rep,), out_specs=rep,
            axis_names=set(dp_axes), check_vma=False,
        )
    )(tree)


@dataclasses.dataclass(frozen=True)
class ResizeEvent:
    """One executed topology change — everything needed to replay it."""

    kind: str  # 'shrink' | 'grow'
    step: int  # global train step at which the resize took effect
    old_dp: int
    new_dp: int
    # new flattened-DP rank -> old flattened-DP rank (None = joined worker)
    keep: tuple
    device_ids: tuple  # device ids of the new mesh (row-major)
    reason: str = ""
    restored_from_checkpoint: bool = False


@dataclasses.dataclass
class ElasticConfig:
    ckpt_every: int = 50
    heartbeat: HeartbeatConfig = dataclasses.field(default_factory=HeartbeatConfig)
    max_restarts: int = 8  # resize budget (legacy name)
    dp_axis: str = "data"
    policy: str = "shrink_on_failure"  # any ELASTIC_POLICIES entry
    step_dt: float = 1.0  # virtual seconds per step (StepClock)
    base_step_time: float = 1.0  # healthy worker's reported step time


def flat_keep_for_shrink(old_mesh, dp_axes, axis: str, kept: Sequence[int]):
    """Flattened-DP keep map after dropping dp-``axis`` slices: new flat
    rank i held old flat rank keep[i]."""
    sizes_o = [old_mesh.shape[a] for a in dp_axes]
    ai = list(dp_axes).index(axis)
    sizes_n = list(sizes_o)
    sizes_n[ai] = len(kept)
    keep = []
    for new_flat in range(int(np.prod(sizes_n))):
        idx = list(np.unravel_index(new_flat, sizes_n))
        idx[ai] = kept[idx[ai]]
        keep.append(int(np.ravel_multi_index(idx, sizes_o)))
    return tuple(keep)


def flat_keep_for_grow(old_mesh, dp_axes, axis: str, n_new: int):
    """Flattened-DP keep map after appending ``n_new`` dp-``axis`` slices:
    survivors keep their positions, joiners map to None."""
    sizes_o = [old_mesh.shape[a] for a in dp_axes]
    ai = list(dp_axes).index(axis)
    old_extent = sizes_o[ai]
    sizes_n = list(sizes_o)
    sizes_n[ai] = old_extent + n_new
    keep = []
    for new_flat in range(int(np.prod(sizes_n))):
        idx = list(np.unravel_index(new_flat, sizes_n))
        if idx[ai] >= old_extent:
            keep.append(None)
        else:
            keep.append(int(np.ravel_multi_index(idx, sizes_o)))
    return tuple(keep)


class ElasticServeController:
    """Drive a :class:`repro.serving.ServeEngine` across replica changes —
    the serving analogue of :class:`ElasticTrainer` (DESIGN.md S15).

    Same harness surface (``kill`` / ``stall`` / ``unstall`` / ``join`` —
    chaos scripts fire against it unchanged), same policy/detector wiring
    on the injected :class:`StepClock`, but the "workers" are the engine's
    *simulated* termination-agreement replicas: a resize produces a
    :class:`ReplicaSet` keep map and calls :meth:`ServeEngine.resize`
    instead of resharding a device mesh.  Unlike training, serving never
    aborts on total failure — :func:`clamp_min_extent` pins the pool at
    ``min_extent`` replicas and spared replicas are pressed back into
    service until joiners restore headroom.

    One controller step = one policy pass + one engine step (which runs up
    to ``steps_per_dispatch`` device ticks); chaos events are matched
    against the engine's *tick* clock via ``apply_due``, so an event due at
    an intermediate tick of a fused dispatch fires at the next dispatch
    boundary — the first point a real control plane could act."""

    def __init__(
        self,
        engine,
        policy: str = "grow_on_join",
        *,
        heartbeat: Optional[HeartbeatConfig] = None,
        clock: Optional[StepClock] = None,
        replica_ids: Optional[Sequence[int]] = None,
        min_extent: int = 1,
        base_step_time: float = 1.0,
        max_resizes: int = 32,
    ):
        self.engine = engine
        ids = (
            list(replica_ids) if replica_ids is not None
            else list(range(engine.dp))
        )
        if len(ids) != engine.dp:
            raise ValueError(
                f"{len(ids)} replica ids for a dp={engine.dp} engine"
            )
        self.replicas = ReplicaSet(ids)
        # spawn(): stateful policies (sla_autoscale hysteresis) get a
        # per-controller instance instead of the shared registry singleton
        self.policy = get_policy(policy).spawn()
        self.clock = clock or StepClock()
        self.detector = FailureDetector(
            ids, heartbeat or HeartbeatConfig(), now=self.clock.now()
        )
        self.min_extent = min_extent
        self.base_step_time = base_step_time
        self.max_resizes = max_resizes
        self.health: dict[int, str] = {r: "ok" for r in ids}
        self.stall_factor: dict[int, float] = {}
        self.pending_joins: list[int] = []

    # -- harness surface (chaos scripts poke these, same as ElasticTrainer) --

    def kill(self, replica_id: int, *, silent: bool = False):
        self.health[replica_id] = "dead"
        if not silent:
            self.detector.mark_dead(replica_id)

    def stall(self, replica_id: int, factor: float = 10.0):
        self.health[replica_id] = "stalled"
        self.stall_factor[replica_id] = factor

    def unstall(self, replica_id: int):
        if self.health.get(replica_id) == "stalled":
            self.health[replica_id] = "ok"
        self.stall_factor.pop(replica_id, None)

    def join(self, replica_ids: Sequence[int]):
        for r in replica_ids:
            if r not in self.pending_joins and r not in self.replicas.ids:
                self.pending_joins.append(r)
                self.health[r] = "ok"

    def _heartbeat_all(self, now: float):
        for r in self.replicas.ids:
            status = self.health.get(r, "ok")
            if status == "dead":
                continue
            step_time = self.base_step_time * (
                self.stall_factor.get(r, 1.0) if status == "stalled" else 1.0
            )
            self.detector.heartbeat(r, now=now, step_time=step_time)

    def _load(self) -> LoadSnapshot:
        """Load picture for autoscaling policies — built by the *engine*
        (:meth:`ServeEngine.load_snapshot`), which also publishes the same
        numbers as telemetry gauges: the trace and the policy see one
        snapshot, never two divergent computations."""
        return self.engine.load_snapshot()

    # -- one controller step -------------------------------------------------

    def step(self, events=None) -> np.ndarray:
        """One policy pass + one engine step.  Returns the retired mask."""
        now = self.clock.advance()
        if events is not None:
            fire = getattr(events, "apply_due", None) or events.apply
            fire(self, self.engine.tick)
        self._heartbeat_all(now)
        decision = self.policy.decide(
            self.detector, now, self.pending_joins,
            frozenset(self.replicas.ids),
            load=self._load(),
        )
        clamped = clamp_min_extent(
            decision, self.replicas.ids, self.min_extent
        )
        if decision.action == "shrink" and clamped is not decision:
            # spared replicas are pressed back into service: clear their
            # failure evidence or the suppressed shrink re-fires forever
            # and blocks join admission
            for r in decision.remove - clamped.remove:
                self.health[r] = "ok"
                self.detector.heartbeat(r, now=now)
        decision = clamped
        if decision.action not in ("none", "abort"):
            obs.instant(
                "elastic.decision",
                action=decision.action,
                reason=decision.reason,
                tick=self.engine.tick,
                dp=self.replicas.dp,
            )
        if decision.action == "abort":
            raise RuntimeError(f"elastic policy abort: {decision.reason}")
        if decision.action == "shrink":
            if len(self.resizes) >= self.max_resizes:
                raise RuntimeError("resize budget exhausted")
            for r in decision.remove:
                self.detector.remove_worker(r)
                self.health[r] = "dead"
            _, keep = self.replicas.remove(decision.remove)
            self.engine.resize(
                self.replicas.dp, keep, reason=decision.reason
            )
        elif decision.action == "grow":
            if len(self.resizes) >= self.max_resizes:
                raise RuntimeError("resize budget exhausted")
            joiners = tuple(decision.admit)
            self.pending_joins = [
                r for r in self.pending_joins if r not in set(joiners)
            ]
            for r in joiners:
                self.detector.add_worker(r, now)
            _, keep = self.replicas.add(joiners)
            self.engine.resize(
                self.replicas.dp, keep, reason=decision.reason
            )
        return self.engine.step()

    @property
    def resizes(self) -> list[ResizeEvent]:
        return self.engine.resizes

    def run(self, requests=None, *, events=None, max_steps: Optional[int] = None):
        """Submit ``requests`` and step the engine under the policy until
        everything retires (the serving analogue of ``ElasticTrainer.run``,
        with chaos ``events`` applied on the engine's tick clock)."""
        eng = self.engine
        for r in requests or []:
            eng.submit(r)
        budget = max_steps or eng.cfg.max_ticks
        steps = 0
        while eng.queue or eng.pending or any(eng.slot_req):
            if steps >= budget:
                raise RuntimeError(
                    f"elastic serve loop did not drain within {budget} steps"
                )
            self.step(events)
            steps += 1
        return eng.results


class ElasticTrainer:
    """Drive training across topology changes.

    ``step_fn_factory(mesh) -> (train_step, init_state, state_specs,
    rules)`` (what ``repro.distributed.gradsync.make_step_factory(cfg,
    tcfg)`` returns); alternatively pass ``(model_cfg, tcfg)`` directly —
    then the factory is built from the ``GRAD_SYNC`` registry **and**
    resizes migrate state in place through the strategy's registered
    resize hook instead of restoring a checkpoint.  With an opaque
    factory the trainer falls back to the legacy checkpoint-restore path
    on every resize (``restores`` counts those).

    ``pipe_factory(mesh)`` builds the data pipeline; its state is
    deterministic and step-keyed, so the token stream continues exactly
    where it stopped regardless of the topology.
    """

    def __init__(
        self,
        mesh,
        step_fn_factory,
        pipe_factory: Callable,
        checkpointer: Optional[Checkpointer] = None,
        cfg: ElasticConfig = None,
        clock: Optional[StepClock] = None,
    ):
        cfg = cfg or ElasticConfig()
        self.train_cfgs = None
        if isinstance(step_fn_factory, tuple):
            from repro.distributed import gradsync

            self.train_cfgs = step_fn_factory
            step_fn_factory = gradsync.make_step_factory(*self.train_cfgs)
        self.mesh = mesh
        self.step_fn_factory = step_fn_factory
        self.pipe_factory = pipe_factory
        self.ck = checkpointer
        self.cfg = cfg
        self.policy = get_policy(cfg.policy)
        self.clock = clock or StepClock(dt=cfg.step_dt)
        self.resizes: list[ResizeEvent] = []
        self.restores = 0  # checkpoint restores actually performed on resize
        # harness-controlled cluster picture
        self.health: dict[int, str] = {}  # device id -> 'ok'|'dead'|'stalled'
        self.stall_factor: dict[int, float] = {}
        self.pending_joins: list[int] = []
        self._build()

    # -- wiring -------------------------------------------------------------

    @property
    def restarts(self) -> int:
        """Resize count (legacy name kept for the pre-S12 API)."""
        return len(self.resizes)

    def device_ids(self) -> tuple[int, ...]:
        return tuple(d.id for d in np.ravel(np.asarray(self.mesh.devices)))

    def _build(self):
        (self.train_step, self.init_state, self.state_specs, self.rules) = (
            self.step_fn_factory(self.mesh)
        )
        self.pipe = self.pipe_factory(self.mesh)
        self._jit = jax.jit(self.train_step)
        now = self.clock.now()
        ids = set(self.device_ids())
        if getattr(self, "detector", None) is None:
            self.detector = FailureDetector(
                list(self.device_ids()), self.cfg.heartbeat, now=now
            )
        else:
            # keep heartbeat history across resizes: a silently-partitioned
            # worker's stale-heartbeat evidence (and a straggler's strike
            # count) must survive unrelated topology changes, or detection
            # restarts from scratch on every resize
            for w in list(self.detector.last):
                if w not in ids:
                    self.detector.remove_worker(w)
            for w in ids:
                self.detector.add_worker(w, now)
        for d in self.device_ids():
            self.health.setdefault(d, "ok")

    def _shardings(self, state):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.state_specs(state)
        )

    def init_or_restore(self, key):
        with self.mesh:
            # initialize straight into the shardings (no device ever holds
            # the whole unsharded train state)
            shardings = self._shardings(jax.eval_shape(self.init_state, key))
            state = jax.jit(self.init_state, out_shardings=shardings)(key)
            latest = self.ck.latest_step() if self.ck else None
            if latest is not None:
                # params + step survive topology changes; optimizer moments
                # restart on reshard (safe default; see fault-tolerance test)
                tpl = {"params": state["params"], "step": state["step"]}
                restored = self.ck.restore(latest, jax.tree.map(
                    lambda x: np.zeros(x.shape, x.dtype), tpl))
                state["params"] = restored["params"]
                state["step"] = jnp.asarray(restored["step"])
                self.pipe.load_state_dict(self.ck.manifest(latest)["extra"]["data"])
            state = jax.device_put(state, shardings)
        return state

    # -- harness surface (chaos scripts poke these) -------------------------

    def kill(self, device_id: int, *, silent: bool = False):
        """Mark a worker dead.  ``silent=True`` models a network partition
        (detected only after the heartbeat timeout elapses on the injected
        clock); the default models a fail-stop crash report (detected on
        the next policy pass)."""
        self.health[device_id] = "dead"
        if not silent:
            self.detector.mark_dead(device_id)

    def stall(self, device_id: int, factor: float = 10.0):
        """Mark a worker as a straggler: it keeps heartbeating, but its
        reported step time is ``factor`` x the healthy baseline."""
        self.health[device_id] = "stalled"
        self.stall_factor[device_id] = factor

    def unstall(self, device_id: int):
        if self.health.get(device_id) == "stalled":
            self.health[device_id] = "ok"
        self.stall_factor.pop(device_id, None)

    def join(self, device_ids: Sequence[int]):
        """Queue workers for admission (policies that grow will admit them
        on their next decision)."""
        for d in device_ids:
            if d not in self.pending_joins:
                self.pending_joins.append(d)
                self.health[d] = "ok"

    def _heartbeat_all(self, now: float):
        for d in self.device_ids():
            status = self.health.get(d, "ok")
            if status == "dead":
                continue
            step_time = self.cfg.base_step_time * (
                self.stall_factor.get(d, 1.0) if status == "stalled" else 1.0
            )
            self.detector.heartbeat(d, now=now, step_time=step_time)

    # -- the ResizeEvent lifecycle ------------------------------------------

    def _clamp_grow(self, decision: ResizeDecision) -> ResizeDecision:
        """Admit only whole DP slices: with TP, a joiner set that is not a
        multiple of the per-slice device count stays pending (admitting it
        would make ``grow_mesh`` raise and kill the run) — the remainder
        is admitted once enough joiners accumulate."""
        per_slice = self.mesh.size // self.mesh.shape[self.cfg.dp_axis]
        n = (len(decision.admit) // per_slice) * per_slice
        if n == 0:
            return ResizeDecision()
        if n < len(decision.admit):
            return dataclasses.replace(decision, admit=decision.admit[:n])
        return decision

    def resize(self, state, decision: ResizeDecision):
        """Execute a policy decision: rebuild the mesh, migrate state in
        place (or restore from checkpoint when no migration path exists),
        rebuild the step functions, and record the :class:`ResizeEvent`."""
        with obs.span(
            "train.resize", action=decision.action, reason=decision.reason
        ) as sp:
            state = self._resize_impl(state, decision)
            if sp is not None:
                ev = self.resizes[-1]
                sp.update(
                    old_dp=ev.old_dp,
                    new_dp=ev.new_dp,
                    step=ev.step,
                    restored=ev.restored_from_checkpoint,
                )
        return state

    def _resize_impl(self, state, decision: ResizeDecision):
        if len(self.resizes) >= self.cfg.max_restarts:
            raise RuntimeError("resize budget exhausted")
        old_mesh = self.mesh
        dp_axes = _dp_axes(old_mesh)
        old_dp = int(np.prod([old_mesh.shape[a] for a in dp_axes]))
        step = int(state["step"]) if state is not None else 0

        if decision.action == "shrink":
            new_mesh, kept = shrink_mesh(
                old_mesh, set(decision.remove), self.cfg.dp_axis
            )
            keep = flat_keep_for_shrink(old_mesh, dp_axes, self.cfg.dp_axis, kept)
            for d in decision.remove:
                self.detector.remove_worker(d)
                self.health[d] = "dead"
        elif decision.action == "grow":
            new_mesh, n_new = grow_mesh(
                old_mesh, tuple(decision.admit), self.cfg.dp_axis
            )
            keep = flat_keep_for_grow(old_mesh, dp_axes, self.cfg.dp_axis, n_new)
            self.pending_joins = [
                d for d in self.pending_joins if d not in set(decision.admit)
            ]
        else:
            raise ValueError(f"resize cannot execute action {decision.action!r}")

        # stale extents invalidate every live plan's memoized derivations
        plans.invalidate_all_plans()

        restored = False
        if state is not None and self.train_cfgs is not None:
            from repro.distributed import gradsync

            cfg, tcfg = self.train_cfgs
            with obs.span("train.resize.migrate", action=decision.action):
                state = gradsync.migrate_state(
                    cfg, tcfg, old_mesh, new_mesh, state, keep
                )
            pipe_state = self.pipe.state_dict()
            self.mesh = new_mesh
            self._build()
            self.pipe.load_state_dict(pipe_state)
            with self.mesh:
                shardings = self._shardings(state)
                state = jax.device_put(state, shardings)
                if decision.action == "grow":
                    # protocol-level param transfer to the joiners: MRD
                    # broadcast at the new (non-power-of-two) extent —
                    # bit-exact, so survivors' params are untouched
                    with obs.span("train.resize.broadcast"):
                        state["params"] = jax.device_put(
                            mrd_broadcast(
                                state["params"], self.mesh,
                                _dp_axes(self.mesh), src=0,
                            ),
                            shardings["params"],
                        )
        else:
            # legacy path (opaque step factory): full checkpoint round-trip
            if self.ck is None:
                raise RuntimeError(
                    "cannot resize: no (model_cfg, tcfg) for in-place "
                    "migration and no checkpointer to restore from"
                )
            self.ck.wait()
            self.mesh = new_mesh
            self._build()
            state = self.init_or_restore(jax.random.PRNGKey(0))
            self.restores += 1
            restored = True

        new_dp = int(np.prod([new_mesh.shape[a] for a in _dp_axes(new_mesh)]))
        self.resizes.append(ResizeEvent(
            kind=decision.action, step=step, old_dp=old_dp, new_dp=new_dp,
            keep=tuple(keep), device_ids=self.device_ids(),
            reason=decision.reason, restored_from_checkpoint=restored,
        ))
        return state

    # -- training loop ------------------------------------------------------

    def handle_failure(self, state, failed_device_ids: set[int]):
        """Immediate shrink (legacy API): the named devices are gone."""
        for d in failed_device_ids:
            self.kill(d)
        return self.resize(
            state,
            ResizeDecision(
                "shrink", remove=frozenset(failed_device_ids),
                reason="handle_failure",
            ),
        )

    def run(self, state, n_steps: int, *, fail_at: Optional[dict] = None,
            events=None):
        """Train for ``n_steps``; returns (state, losses).

        ``fail_at`` = {step: {device_ids}} injects immediate failures
        (legacy test hook).  ``events`` is a chaos script — any object
        with ``apply(trainer, step)`` (see ``tests/chaos.py``) — applied
        before each step on the injected clock.
        """
        losses = []
        i = int(state["step"])
        target = i + n_steps
        while i < target:
            now = self.clock.advance()
            if fail_at and i in fail_at:
                for d in fail_at.pop(i):
                    self.kill(d)
            if events is not None:
                events.apply(self, i)
            self._heartbeat_all(now)
            decision = self.policy.decide(
                self.detector, now, self.pending_joins,
                frozenset(self.device_ids()),
            )
            if decision.action == "abort":
                raise RuntimeError(f"elastic policy abort: {decision.reason}")
            if decision.action == "grow":
                decision = self._clamp_grow(decision)
            if decision.action in ("shrink", "grow"):
                state = self.resize(state, decision)
                i = int(state["step"])
                if i >= target:
                    break
            with self.mesh:
                state, metrics = self._jit(state, self.pipe.next_batch())
            losses.append(float(metrics["loss"]))
            i += 1
            if self.ck is not None and i % self.cfg.ckpt_every == 0:
                self.ck.save(i, state, extra={"data": self.pipe.state_dict()})
        if self.ck is not None:
            self.ck.save(int(state["step"]), state,
                         extra={"data": self.pipe.state_dict()}, block=True)
        return state, losses
