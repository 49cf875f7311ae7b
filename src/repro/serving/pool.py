"""Layer 1 of the serving subsystem: fixed-slot decode *pools*.

A pool is the device-resident half of continuous batching: a fixed number
of slots (the jitted batch dimension — shapes never change, so admission
never recompiles) over the existing sharded KV/state cache, with per-slot
``lengths`` / ``active`` / ``age`` state and slot recycling — a retired
slot is re-used by offset-prefilling the next request into that slot's
cache slice while every other slot keeps decoding.

Two pools, one per workload family:

- :class:`DecodePool` — LLM decode over ``transformer.init_cache`` and the
  per-slot-length ``make_pool_decode_step`` /
  ``make_slot_prefill_step`` builders in ``repro.distributed.serve``.
  Slot math is an independent vmap lane per request, so a request's
  greedy tokens are bit-identical to decoding it alone in a static batch
  (tested in ``tests/test_serving.py``).
- :class:`FixedPointPool` — per-request fixed-point solves (the
  D-iteration serving workload): every slot carries its own iterate and
  affine payload (personalization vector / right-hand side) over one
  shared operator, one fused vmapped update per tick, block residuals
  reported per termination replica.

Pools own the device state and the jitted admission step; the engine owns
the host-side control plane (``active`` / token counters / ages) and
drives ``device_step`` inside its fused per-tick dispatch.  Schedulers
and termination protocols never touch the cache directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.asynchrony.protocols import RES_INIT
from repro.distributed import serve as dserve
from repro.models import transformer
from repro.models.config import ModelConfig


class DecodePool:
    """Fixed-slot continuous-batching pool over the sharded decode cache."""

    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        *,
        slots: int,
        max_len: int,
        max_prompt_len: int,
    ):
        if max_prompt_len >= max_len:
            raise ValueError("max_prompt_len must leave room to decode")
        self.cfg, self.mesh = cfg, mesh
        self.slots, self.max_len, self.max_prompt_len = slots, max_len, max_prompt_len
        pool_step, self.rules = dserve.make_pool_decode_step(cfg, mesh)
        slot_prefill, _ = dserve.make_slot_prefill_step(cfg, mesh, max_prompt_len)
        self.prefill_path = dserve.prefill_path(
            cfg, jax.eval_shape(lambda: transformer.init_cache(cfg, 1, max_len)),
            max_prompt_len,
        )

        def _step(params, state, active):
            logits, cache2 = pool_step(
                params, state["tokens"], state["cache"], state["lengths"]
            )
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            # freeze slots at cache capacity (the engine retires them; the
            # clamp only keeps the rolling write from wrapping meanwhile)
            adv = active & (state["lengths"] < self.max_len - 1)
            return {
                "cache": dserve.select_slots(active, cache2, state["cache"]),
                "tokens": jnp.where(active, nxt, state["tokens"]),
                "lengths": jnp.where(adv, state["lengths"] + 1, state["lengths"]),
            }

        # pure traced step — the engine fuses this with the termination
        # protocol's tick into one dispatch per engine tick
        self.device_step = _step

        def _admit(params, state, prompt, plen, slot):
            last_logits, cache = slot_prefill(
                params, prompt, plen, state["cache"], slot
            )
            tok0 = jnp.argmax(last_logits, -1).astype(jnp.int32)
            return {
                "cache": cache,
                "tokens": state["tokens"].at[slot].set(tok0),
                "lengths": state["lengths"].at[slot].set(plen),
            }

        self._jadmit = jax.jit(_admit)
        self.reset()

    def reset(self):
        from jax.sharding import NamedSharding, PartitionSpec

        with self.mesh:
            cache = transformer.init_cache(self.cfg, self.slots, self.max_len)
        # commit every array to its sharding up front: jit caches key on
        # argument shardings, so uncommitted fresh state next to committed
        # stepped state would silently compile the pool step twice
        specs = dserve.cache_specs(self.cfg, self.rules, cache)
        cache = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            cache, specs,
        )
        rep = NamedSharding(self.mesh, PartitionSpec())
        self.state = {
            "cache": cache,
            "tokens": jax.device_put(jnp.zeros((self.slots,), jnp.int32), rep),
            "lengths": jax.device_put(jnp.zeros((self.slots,), jnp.int32), rep),
        }

    def capacity_mask(self, state):
        """Traced: slots frozen at the cache capacity clamp."""
        return state["lengths"] >= self.max_len - 1

    @property
    def cache_bytes(self) -> int:
        return int(
            sum(l.nbytes for l in jax.tree.leaves(self.state["cache"]))
        )

    def admit(self, params, prompt, slot: int) -> int:
        """Offset-prefill ``prompt`` (1-D int array) into ``slot``.

        Returns the request's first generated token (greedy argmax of the
        prefill's last-position logits).
        """
        prompt = np.asarray(prompt, np.int32)
        plen = int(prompt.shape[0])
        if not 0 < plen <= self.max_prompt_len:
            raise ValueError(
                f"prompt length {plen} not in (0, {self.max_prompt_len}]"
            )
        padded = np.zeros((self.max_prompt_len,), np.int32)
        padded[:plen] = prompt
        # the dispatch and its first token
        with obs.span("pool.prefill", path=self.prefill_path):
            with self.mesh:
                self.state = self._jadmit(
                    params, self.state, jnp.asarray(padded), jnp.int32(plen),
                    jnp.int32(slot),
                )
            return int(self.state["tokens"][slot])


class FixedPointPool:
    """Per-request fixed-point solves in pool slots (D-iteration serving).

    All requests share one operator (``base.full_map``); a request is its
    affine payload ``v`` (personalization vector / right-hand side):
    ``f(x, v) = base(x) + gain * (v - v0)``, which is exact for the linear
    solvers this serves (``d_iteration``: ``gain = 1 - damping``;
    weighted-Jacobi families: ``gain = omega / diag``).  One vmapped update
    advances every active slot per tick; residuals are reported per
    ``dp``-replica block for the agreement reduction.
    """

    def __init__(self, base, *, slots: int, dp: int, gain, payload0=None):
        if base.n % dp:
            raise ValueError(f"n={base.n} must divide into dp={dp} blocks")
        self.base, self.slots, self.dp = base, slots, dp
        self.n = base.n
        gain = jnp.asarray(gain, jnp.float32)
        v0 = (
            jnp.zeros((self.n,), jnp.float32)
            if payload0 is None
            else jnp.asarray(payload0, jnp.float32)
        )

        def param_map(x, v):
            return base.full_map(x) + gain * (v - v0)

        self.param_map = param_map
        self._build_step()

        def _admit(state, v, slot):
            return {
                "x": state["x"].at[slot].set(jnp.zeros((self.n,), jnp.float32)),
                "payload": state["payload"].at[slot].set(v),
            }

        self._jadmit = jax.jit(_admit)
        self.reset()

    def _build_step(self):
        """(Re)build the vmapped tick at the current replica extent: the
        residual block reshape is the only dp-dependent piece of the pool."""
        dp, m = self.dp, self.n // self.dp

        def _step(state, active):
            xnew = jax.vmap(self.param_map)(state["x"], state["payload"])
            upd = jnp.max(
                jnp.abs(xnew - state["x"]).reshape(self.slots, dp, m), axis=2
            )  # [S, dp]
            x = jnp.where(active[:, None], xnew, state["x"])
            residual = jnp.where(active[:, None], upd, RES_INIT).T  # [dp, S]
            return {**state, "x": x}, residual

        self.device_step = _step

    def migrate_dp(self, new_dp: int) -> None:
        """Elastic resize: re-block the residual report at the new extent.

        The per-slot iterates and payloads are replica-independent (every
        replica holds the same ``x``), so only the reporting reshape
        changes — requests keep iterating exactly where they were.
        """
        if self.n % new_dp:
            raise ValueError(f"n={self.n} must divide into dp={new_dp} blocks")
        self.dp = new_dp
        self._build_step()

    def reset(self):
        self.state = {
            "x": jnp.zeros((self.slots, self.n), jnp.float32),
            "payload": jnp.zeros((self.slots, self.n), jnp.float32),
        }

    def admit(self, payload, slot: int) -> None:
        v = jnp.asarray(np.asarray(payload, np.float32))
        if v.shape != (self.n,):
            raise ValueError(f"payload shape {v.shape} != ({self.n},)")
        self.state = self._jadmit(self.state, v, jnp.int32(slot))

    def solution(self, slot: int) -> np.ndarray:
        return np.asarray(self.state["x"][slot])
