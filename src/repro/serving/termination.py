"""Layer 3 of the serving subsystem: request *termination* (``TERMINATION``).

Deciding *when each in-flight request is done* without a global barrier is
exactly the paper's distributed convergence-detection problem, so this layer
is built from the same parts as ``repro.asynchrony.protocols``: a
non-blocking MRD :class:`~repro.collectives.plans.CollectivePlan` advanced
one stage per engine tick, plus the per-worker contribution policies of
``DETECTION_PROTOCOLS`` (re-used directly — ``residual_interval`` vmaps the
``interval`` protocol's windowed latch over replicas x slots).

With ``dp > 1`` replicas, the per-slot done decision is **agreed**: every
replica contributes its local view (its block residual, or its local
EOS/max-len bit) into a staged MRD max-reduction over the ``[dp]`` axis —
non-power-of-two ``dp`` works natively, the paper's point — and a slot
retires only when a reduction cycle completes and certifies it.  Because
retirement is a pure function of the *agreed* result's completion tick, all
replicas retire the same slots on the same tick by construction; with
``dp = 1`` the plan has zero stages and every tick certifies immediately.

Slot recycling is handled without tagging the wire payload: each cycle
latches contributions at its start tick (``t_latch``), and a completed
cycle may only retire requests admitted **at or before** that latch — a
request prefilled into a recycled slot mid-cycle can never be killed by its
predecessor's agreed done-bit.

Registered protocols:

- ``eos_maxlen`` — LLM decode: done when the last token equals the
  request's EOS id or the generation budget is exhausted.
- ``residual_inexact`` — fixed-point requests, paper Alg. 1: replicas
  contribute their instantaneous block-update magnitude; certify when the
  agreed max drops below the request's eps.
- ``residual_interval`` — windowed Alg. 1 (the hardened protocol): each
  replica contributes the max over its last ``window`` magnitudes, so one
  momentarily-small update cannot retire a request; the default window
  covers a full agreement cycle.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.asynchrony.protocols import RES_INIT, get_protocol
from repro.collectives import plans

TERMINATION: Dict[str, Any] = {}


def register_termination(name: str):
    def deco(cls):
        TERMINATION[name] = cls()
        return cls

    return deco


def get_termination(name: str):
    try:
        return TERMINATION[name]
    except KeyError:
        raise ValueError(
            f"unknown termination protocol {name!r}; "
            f"registered: {sorted(TERMINATION)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class TerminationConfig:
    """Static config for a termination protocol (hashable: jit-friendly)."""

    dp: int = 1  # replica count the done decision is agreed across
    eps: float = 1e-6  # default residual threshold (requests may override)
    window: int = 0  # residual_interval: 0 -> one full agreement cycle + 1
    schedule: str = "mrd"  # any repro.collectives SCHEDULES entry


def make_signals(
    *, tokens, new_tokens, eos, max_new, eps, active, admit_tick, tick, residual
):
    """The per-tick observation dict every protocol's ``tick`` consumes.

    ``tokens``/``new_tokens``/``eos``/``max_new``/``admit_tick``: ``[S]``
    int32; ``eps``: ``[S]`` float32 per-request thresholds; ``active``:
    ``[S]`` bool; ``tick``: scalar; ``residual``: ``[dp, S]`` float32 —
    each replica's block-local update magnitude (zeros for LLM decode).
    """
    return {
        "tokens": tokens, "new_tokens": new_tokens, "eos": eos,
        "max_new": max_new, "eps": eps, "active": active,
        "admit_tick": admit_tick, "tick": tick, "residual": residual,
    }


class _TerminationBase:
    """Shared agreement machinery: one staged MRD max-reduction over dp."""

    def _plan(self, cfg: TerminationConfig) -> plans.CollectivePlan:
        return plans.allreduce_plan(schedule=cfg.schedule, p=cfg.dp, op="max")

    def cycle_length(self, cfg: TerminationConfig) -> int:
        return self._plan(cfg).cycle_length()

    def _agree(self, st, cfg, sig, contribution):
        """Advance the non-blocking reduction one stage.

        Returns ``(new_nb, t_latch, flag, agreed [S])`` where ``agreed`` is
        the replica-agreed reduction of the contributions latched at
        ``t_latch`` (valid only when ``flag``).
        """
        plan = self._plan(cfg)
        starting = st["nb"]["stage"] == 0
        t_latch = jnp.where(starting, sig["tick"], st["t_latch"])
        with jax.named_scope("termination.agree"):
            nb = plan.step(st["nb"], contribution)
        return nb, t_latch, nb["flag"], nb["result"][0]

    def _guard(self, sig, t_latch):
        """Only requests admitted at or before the cycle's latch may retire."""
        return sig["active"] & (sig["admit_tick"] <= t_latch)

    # -- elastic resize (DESIGN.md S15) --------------------------------------

    def migrate(self, st, keep, cfg: TerminationConfig, slots: int):
        """Re-agree in-flight slot state after the replica extent changes.

        ``keep[i]`` is the old replica now at new rank ``i`` (None = a
        joiner); ``cfg`` is the config at the *new* extent.  The staged
        reduction is abandoned — its stage counter and partial combines are
        meaningless at the new extent, whose MRD cycle length differs — and
        restarts from stage 0, so the next tick re-latches ``t_latch`` to
        the current tick and every pre-resize admission stays retirable
        (``admit_tick <= t_latch`` still holds: no re-prefill needed).
        Everything certified so far survives; retirement requires a full
        fresh cycle of agreement among the *new* replica set.
        """
        new = self.init(cfg, slots)
        new["certified"] = st["certified"]
        return new


def _migrate_replica_rows(old_leaf, fresh_leaf, keep):
    """Select per-replica monitor rows (axis 0) along the resize keep map.

    Joiners take the fresh (RES_INIT-saturated) row, so they cannot help
    certify a slot before observing a whole window themselves.  When the
    per-row shape differs across extents (``window=0`` derives the window
    from the cycle length, which changes with dp), a survivor's new window
    is refilled with its running max — conservative by construction: the
    row's contribution can only be >= what it was, never optimistic.
    """
    parts = []
    for k in keep:
        if k is None:
            parts.append(fresh_leaf[0])
        elif old_leaf.shape[1:] == fresh_leaf.shape[1:]:
            parts.append(old_leaf[k])
        else:
            row_max = jnp.max(old_leaf[k], axis=-1, keepdims=True)
            parts.append(
                jnp.broadcast_to(row_max, fresh_leaf.shape[1:]).astype(
                    fresh_leaf.dtype
                )
            )
    return jnp.stack(parts)


@register_termination("eos_maxlen")
class EosMaxlenTermination(_TerminationBase):
    """LLM decode termination: EOS token or generation budget, agreed."""

    name = "eos_maxlen"

    def init(self, cfg: TerminationConfig, slots: int):
        return {
            "nb": self._plan(cfg).init(jnp.zeros((cfg.dp, slots), jnp.float32)),
            "t_latch": jnp.zeros((), jnp.int32),
            "certified": jnp.zeros((slots,), jnp.float32),
        }

    def tick(self, st, sig, cfg: TerminationConfig):
        local = sig["active"] & (
            (sig["tokens"] == sig["eos"]) | (sig["new_tokens"] >= sig["max_new"])
        )
        contribution = jnp.broadcast_to(
            local.astype(jnp.float32)[None, :], (cfg.dp, local.shape[0])
        )
        nb, t_latch, flag, agreed = self._agree(st, cfg, sig, contribution)
        retire = flag & (agreed >= 0.5) & self._guard(sig, t_latch)
        certified = jnp.where(retire, agreed, st["certified"])
        return {"nb": nb, "t_latch": t_latch, "certified": certified}, retire


class _ResidualTermination(_TerminationBase):
    """Residual-certified termination for fixed-point requests.

    Delegates the per-(replica, slot) contribution policy to the matching
    ``DETECTION_PROTOCOLS`` entry (``policy``) — the same latching code the
    sim engine and the training-loop ConvergenceMonitor run.
    """

    policy = "inexact"

    def _window(self, cfg: TerminationConfig) -> int:
        return cfg.window if cfg.window else self.cycle_length(cfg) + 1

    def _policy_init(self, cfg: TerminationConfig, dp: int, slots: int):
        proto = get_protocol(self.policy)
        metric0 = jnp.full((), RES_INIT, jnp.float32)
        if self.policy == "interval":
            one = proto.monitor_init(metric0, window=self._window(cfg))
        else:
            one = proto.monitor_init(metric0)
        return jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (dp, slots) + leaf.shape), one
        )

    def init(self, cfg: TerminationConfig, slots: int):
        return {
            "nb": self._plan(cfg).init(
                jnp.full((cfg.dp, slots), RES_INIT, jnp.float32)
            ),
            "m": self._policy_init(cfg, cfg.dp, slots),
            "t_latch": jnp.zeros((), jnp.int32),
            "certified": jnp.full((slots,), RES_INIT, jnp.float32),
        }

    def tick(self, st, sig, cfg: TerminationConfig):
        proto = get_protocol(self.policy)
        slots = sig["active"].shape[0]

        # a slot admitted this tick restarts its policy state (the window
        # refills before the new request can certify)
        fresh = self._policy_init(cfg, cfg.dp, slots)
        admitted_now = sig["admit_tick"] == sig["tick"]
        m = jax.tree.map(
            lambda cur, f: jnp.where(
                admitted_now.reshape((1, slots) + (1,) * (cur.ndim - 2)), f, cur
            ),
            st["m"], fresh,
        )

        def contribute(mstate, metric):
            return proto.monitor_contribution(
                mstate, metric, sig["tick"], self.cycle_length(cfg)
            )

        m, contribution = jax.vmap(jax.vmap(contribute))(m, sig["residual"])
        nb, t_latch, flag, agreed = self._agree(st, cfg, sig, contribution)
        retire = flag & (agreed < sig["eps"]) & self._guard(sig, t_latch)
        certified = jnp.where(retire, agreed, st["certified"])
        return {
            "nb": nb, "m": m, "t_latch": t_latch, "certified": certified,
        }, retire

    def migrate(self, st, keep, cfg: TerminationConfig, slots: int):
        new = super().migrate(st, keep, cfg, slots)
        new["m"] = jax.tree.map(
            lambda o, f: _migrate_replica_rows(o, f, keep), st["m"], new["m"]
        )
        return new


@register_termination("residual_inexact")
class ResidualInexactTermination(_ResidualTermination):
    name = "residual_inexact"
    policy = "inexact"


@register_termination("residual_interval")
class ResidualIntervalTermination(_ResidualTermination):
    name = "residual_interval"
    policy = "interval"
