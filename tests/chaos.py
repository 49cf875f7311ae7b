"""Deterministic chaos-testing harness for the elastic runtime (DESIGN.md
S12).

Two pieces:

- a seeded **event-script DSL** (:class:`Kill` / :class:`Join` /
  :class:`Stall` / :class:`Unstall` composed into a :class:`ChaosScript`)
  that the :class:`repro.runtime.ElasticTrainer` applies before each train
  step.  Everything flows through the *injected clock* of the
  ``FailureDetector`` — a silent kill is detected exactly when the virtual
  heartbeat timeout elapses, a straggler is drained after exactly
  ``evict_after_straggler_steps`` slow steps — so a script determines the
  full resize trajectory bit-for-bit, with no wall-clock nondeterminism.
  :meth:`ChaosScript.random` generates *legal* seeded sequences (never
  killing the last worker, only joining devices that exist and are
  currently outside the mesh).

- an **oracle replay** (:func:`oracle_replay`): the same model/config
  trained with plain ``jax.jit`` steps — no policies, no detector, no
  harness — as a chain of uninterrupted runs at each intermediate extent,
  stitched with the same ``gradsync.migrate_state`` calls the trainer's
  recorded :class:`ResizeEvent` s describe.  The chaos suite asserts the
  chaotic run's params are **bit-identical** to this straight-line
  replay: the entire elastic machinery (detection, policies, plan
  invalidation, MRD param broadcast on grow) adds nothing to the math
  beyond the migrations themselves.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Event DSL
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Kill:
    """Worker ``device`` dies before step ``step``.  ``silent=True`` models
    a network partition: detection waits for the heartbeat timeout on the
    virtual clock instead of a fail-stop crash report."""

    step: int
    device: int
    silent: bool = False

    def fire(self, trainer):
        trainer.kill(self.device, silent=self.silent)


@dataclasses.dataclass(frozen=True)
class Join:
    """Workers ``devices`` ask to join before step ``step`` (admitted by
    growth-capable policies on their next decision)."""

    step: int
    devices: tuple

    def fire(self, trainer):
        trainer.join(tuple(self.devices))


@dataclasses.dataclass(frozen=True)
class Stall:
    """Worker ``device`` slows to ``factor`` x the healthy step time."""

    step: int
    device: int
    factor: float = 10.0

    def fire(self, trainer):
        trainer.stall(self.device, self.factor)


@dataclasses.dataclass(frozen=True)
class Unstall:
    step: int
    device: int

    def fire(self, trainer):
        trainer.unstall(self.device)


class ChaosScript:
    """An ordered event script; ``apply`` is the hook
    :meth:`repro.runtime.ElasticTrainer.run` calls before each step."""

    def __init__(self, events: Sequence):
        self.events = sorted(events, key=lambda e: e.step)
        self.fired: list = []

    def apply(self, trainer, step: int):
        for ev in self.events:
            if ev.step == step and ev not in self.fired:
                ev.fire(trainer)
                self.fired.append(ev)

    def apply_due(self, trainer, step: int):
        """Fire every not-yet-fired event with ``ev.step <= step``.

        The serving engine's tick jumps by ``steps_per_dispatch`` per fused
        call, so exact-step matching (``apply``) would skip events that land
        inside a dispatch window; controllers that observe a coarse clock
        use this hook instead."""
        for ev in self.events:
            if ev.step <= step and ev not in self.fired:
                ev.fire(trainer)
                self.fired.append(ev)

    @staticmethod
    def random(
        seed: int,
        n_steps: int,
        initial_devices: Sequence[int],
        spare_devices: Sequence[int] = (),
        min_extent: int = 2,
        max_events: int = 4,
        event_steps: Optional[Sequence[int]] = None,
    ) -> "ChaosScript":
        """Seeded *legal* kill/join sequence: tracks the live worker set so
        it never kills below ``min_extent`` and only joins devices that are
        currently outside the mesh."""
        rng = np.random.default_rng(seed)
        live = list(initial_devices)
        outside = list(spare_devices)
        steps = (
            sorted(rng.choice(np.arange(1, n_steps), size=max_events, replace=False))
            if event_steps is None
            else list(event_steps)
        )
        events: list = []
        for s in steps[:max_events]:
            can_kill = len(live) > min_extent
            can_join = len(outside) > 0
            if not (can_kill or can_join):
                break
            if can_kill and (not can_join or rng.random() < 0.5):
                victim = live[int(rng.integers(len(live)))]
                events.append(Kill(int(s), victim))
                live.remove(victim)
                outside.append(victim)
            else:
                n = int(rng.integers(1, min(2, len(outside)) + 1))
                joiners = [outside.pop(int(rng.integers(len(outside))))
                           for _ in range(n)]
                events.append(Join(int(s), tuple(sorted(joiners))))
                live.extend(joiners)
        return ChaosScript(events)


# ---------------------------------------------------------------------------
# Oracle replay: uninterrupted runs at each intermediate extent
# ---------------------------------------------------------------------------


def _mesh_from_ids(device_ids):
    from jax.sharding import AxisType

    by_id = {d.id: d for d in jax.devices()}
    devs = [by_id[i] for i in device_ids]
    return jax.make_mesh(
        (len(devs),), ("data",), devices=devs,
        axis_types=(AxisType.Auto,),
    )


def oracle_replay(
    cfg,
    tcfg,
    dcfg,
    initial_device_ids: Sequence[int],
    resizes: Sequence,
    n_steps: int,
    *,
    key=None,
):
    """Replay a recorded resize trajectory with plain jitted train steps.

    Each segment is an *uninterrupted oracle run at that extent* — built
    straight from ``step_lib.make_train_step`` with none of the elastic
    machinery — and segments are stitched with the same
    ``gradsync.migrate_state`` calls the recorded :class:`ResizeEvent` s
    name.  Returns ``(state, losses)``; DP-only (1-D ``("data",)``)
    meshes.
    """
    from jax.sharding import NamedSharding

    from repro.data.pipeline import SyntheticPipeline
    from repro.distributed import gradsync
    from repro.distributed import step as step_lib

    key = jax.random.PRNGKey(0) if key is None else key
    by_step: dict[int, list] = {}
    for ev in resizes:
        by_step.setdefault(int(ev.step), []).append(ev)

    mesh = _mesh_from_ids(initial_device_ids)
    train_step, init_state, state_specs, _ = step_lib.make_train_step(cfg, mesh, tcfg)
    with mesh:
        # the same sharded jitted init as ElasticTrainer.init_or_restore
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            state_specs(jax.eval_shape(init_state, key)),
        )
        state = jax.jit(init_state, out_shardings=shardings)(key)
    pipe = SyntheticPipeline(cfg, dcfg, mesh)
    jit_step = jax.jit(train_step)
    losses = []
    for i in range(n_steps):
        for ev in by_step.get(i, []):
            old_mesh, new_mesh = mesh, _mesh_from_ids(ev.device_ids)
            state = gradsync.migrate_state(
                cfg, tcfg, old_mesh, new_mesh, state, ev.keep
            )
            mesh = new_mesh
            train_step, init_state, state_specs, _ = step_lib.make_train_step(
                cfg, mesh, tcfg
            )
            jit_step = jax.jit(train_step)
            pipe_state = pipe.state_dict()
            pipe = SyntheticPipeline(cfg, dcfg, mesh)
            pipe.load_state_dict(pipe_state)
            with mesh:
                state = jax.device_put(
                    state,
                    jax.tree.map(
                        lambda s: NamedSharding(mesh, s), state_specs(state)
                    ),
                )
        with mesh:
            state, metrics = jit_step(state, pipe.next_batch())
        losses.append(float(metrics["loss"]))
    return state, losses


def assert_params_bit_identical(a, b, context: str = ""):
    """Bitwise equality of two param pytrees (elementwise, every leaf)."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"param tree structures differ {context}"
    for x, y in zip(la, lb):
        xa = np.asarray(jax.device_get(x))
        ya = np.asarray(jax.device_get(y))
        assert xa.dtype == ya.dtype and xa.shape == ya.shape, context
        if not np.array_equal(
            xa.view(np.uint8) if xa.dtype == jnp.bfloat16 else xa,
            ya.view(np.uint8) if ya.dtype == jnp.bfloat16 else ya,
        ):
            bad = np.abs(xa.astype(np.float64) - ya.astype(np.float64)).max()
            raise AssertionError(
                f"params not bit-identical {context}: max abs diff {bad}"
            )
