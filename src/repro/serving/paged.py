"""Layer 1b of the serving subsystem: the *block-paged* decode pool.

The contiguous :class:`repro.serving.pool.DecodePool` reserves a full
``max_len`` cache slice per slot, so memory and occupancy are capped by the
worst-case request.  Here the cache is a shared physical pool of fixed-size
blocks (``distributed.serve.init_paged_pool``):

- :class:`BlockAllocator` — host-side free-list with refcounts and a
  content-addressed prefix registry.  Identical system prompts map their
  full prefix blocks to the *same* physical blocks (stored once, refcounted
  per sharer); :meth:`BlockAllocator.fork_private` is the copy-on-write
  primitive guarding any block a request may write.
- :class:`PagedDecodePool` — the device half.  Admission plans blocks for
  the request's *whole* budget up front (``ceil((plen+max_new+1)/bs)``), so
  the engine's multi-tick fused dispatch never faults on a missing block;
  a per-slot ``slot_cap`` freezes lengths at the reservation edge exactly
  like the contiguous pool's ``max_len`` clamp.  Decode gathers each slot's
  blocks into a view of exactly the contiguous layout and runs the
  *unchanged* per-slot decode vmap — which is what makes paged decode
  bit-identical to contiguous decode token-for-token (tested in
  ``tests/test_serving_paged.py``).  ``attn="pallas"`` switches the fused
  tick to :func:`repro.models.transformer.forward_decode_paged`, reading
  K/V through the block table inside the Pallas paged-attention kernel.

Block 0 is reserved as the trash block: device writes for inactive slots
are redirected there, so the fused tick stays one dispatch with no host
branching on allocator state.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed import serve as dserve
from repro.models import transformer
from repro.models.config import ModelConfig


class BlockAllocator:
    """Free-list block allocator with refcounts and a prefix registry.

    Block ids are ``1..num_blocks-1`` (0 is the reserved trash block).
    Invariants (checked by :meth:`check`, property-tested in
    ``tests/test_paged_allocator.py``):

    - a block is on the free list iff its refcount is 0;
    - a block is never handed out twice while allocated;
    - a registered prefix key always points at a live (refcount > 0)
      block, and is dropped exactly when the last sharer releases it.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least 1 usable block + the trash block")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() from the end -> lowest ids first (deterministic layouts)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.ref = np.zeros((num_blocks,), np.int64)
        self.ref[0] = 1  # trash block: permanently pinned
        self._block_of: Dict[bytes, int] = {}  # prefix key -> block id
        self._key_of: Dict[int, bytes] = {}    # block id -> prefix key

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Live blocks excluding the trash block."""
        return self.num_blocks - 1 - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise MemoryError(
                f"out of cache blocks ({self.num_blocks - 1} usable)"
            )
        b = self._free.pop()
        self.ref[b] = 1
        return b

    def retain(self, bid: int) -> None:
        if bid == 0 or self.ref[bid] <= 0:
            raise ValueError(f"retain of unallocated block {bid}")
        self.ref[bid] += 1

    def release(self, bid: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        if bid == 0 or self.ref[bid] <= 0:
            raise ValueError(f"release of unallocated block {bid}")
        self.ref[bid] -= 1
        if self.ref[bid] == 0:
            key = self._key_of.pop(bid, None)
            if key is not None:
                del self._block_of[key]
            self._free.append(bid)
            return True
        return False

    # -- prefix sharing ------------------------------------------------------

    def lookup(self, key: bytes) -> Optional[int]:
        """Adopt the block registered under ``key`` (bumps its refcount)."""
        bid = self._block_of.get(key)
        if bid is not None:
            self.ref[bid] += 1
        return bid

    def peek(self, key: bytes) -> Optional[int]:
        """Registry lookup without taking a reference (capacity planning)."""
        return self._block_of.get(key)

    def register(self, key: bytes, bid: int) -> None:
        """Publish ``bid`` (which the caller holds) as the block for ``key``."""
        if bid == 0 or self.ref[bid] <= 0:
            raise ValueError(f"register of unallocated block {bid}")
        if key in self._block_of:
            return  # first registration wins (content is identical anyway)
        self._block_of[key] = bid
        self._key_of[bid] = key

    def fork_private(self, bid: int) -> Tuple[int, bool]:
        """Copy-on-write: return a block id the caller may safely write.

        If the caller is the only owner, that's ``(bid, False)``.  If the
        block is shared, the caller's reference moves to a fresh private
        block — ``(new_bid, True)`` — and the shared block (and every other
        sharer's view of it) is left untouched.  The caller is responsible
        for filling the new block (admission refills it from the prompt
        recompute, so no device-side copy is needed).
        """
        if self.ref[bid] == 1:
            return bid, False
        nb = self.alloc()  # before release: MemoryError must not leak the ref
        self.release(bid)
        return nb, True

    # -- elastic resize (DESIGN.md S15) --------------------------------------

    def export_arrays(self) -> Dict[str, np.ndarray]:
        """Allocator state as flat int arrays — the broadcastable form a
        joining replica adopts.  The free list is exported *in order* (pop
        order determines future block layouts, so a joiner must replay it
        exactly); the prefix registry is packed as concatenated key bytes +
        per-key lengths + block ids, sorted by key for determinism.  All
        ids are int32 (x64 is off; int64 leaves would be silently coerced).
        """
        keys = sorted(self._block_of.items())
        return {
            "ref": self.ref.astype(np.int32),
            "free": np.asarray(self._free, np.int32),
            "key_bytes": np.frombuffer(
                b"".join(k for k, _ in keys), np.uint8
            ).copy(),
            "key_lens": np.asarray([len(k) for k, _ in keys], np.int32),
            "key_blocks": np.asarray([b for _, b in keys], np.int32),
        }

    @classmethod
    def from_arrays(
        cls, arrays: Dict[str, np.ndarray], num_blocks: int, block_size: int
    ) -> "BlockAllocator":
        """Rebuild an allocator from :meth:`export_arrays` output."""
        a = cls(num_blocks, block_size)
        a.ref = np.asarray(arrays["ref"]).astype(np.int64).copy()
        a._free = [int(b) for b in np.asarray(arrays["free"])]
        packed = np.asarray(arrays["key_bytes"], np.uint8).tobytes()
        off = 0
        for ln, bid in zip(
            np.asarray(arrays["key_lens"]), np.asarray(arrays["key_blocks"])
        ):
            key = packed[off : off + int(ln)]
            off += int(ln)
            a._block_of[key] = int(bid)
            a._key_of[int(bid)] = key
        a.check()
        return a

    def check(self) -> None:
        """Assert the allocator invariants (test hook)."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate block on free list"
        assert 0 not in free, "trash block on free list"
        assert self.ref[0] >= 1, "trash block unpinned"
        for b in range(1, self.num_blocks):
            assert (self.ref[b] == 0) == (b in free), (
                f"block {b}: ref={self.ref[b]} free={b in free}"
            )
        for key, b in self._block_of.items():
            assert self._key_of.get(b) == key, f"registry asymmetry at {b}"
            assert self.ref[b] > 0, f"registered block {b} is free"


class PagedDecodePool:
    """Block-paged continuous-batching pool (drop-in for ``DecodePool``)."""

    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        *,
        slots: int,
        max_len: int,
        max_prompt_len: int,
        block_size: int = 8,
        num_blocks: Optional[int] = None,
        share_prefixes: bool = True,
        attn: str = "gather",
    ):
        if max_prompt_len >= max_len:
            raise ValueError("max_prompt_len must leave room to decode")
        if max_len % block_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of block_size={block_size}"
            )
        dserve.validate_pageable(cfg, max_len)
        self.cfg, self.mesh = cfg, mesh
        self.slots, self.max_len, self.max_prompt_len = slots, max_len, max_prompt_len
        self.block_size = block_size
        self.blocks_per_slot = max_len // block_size
        if num_blocks is None:
            # capacity parity with the contiguous pool (+ the trash block)
            num_blocks = slots * self.blocks_per_slot + 1
        self.num_blocks = num_blocks
        self.share_prefixes = share_prefixes
        pool_step, self.rules = dserve.make_paged_pool_decode_step(
            cfg, mesh, block_size, attn=attn
        )
        slot_prefill, _ = dserve.make_paged_slot_prefill_step(
            cfg, mesh, max_prompt_len, max_len, block_size
        )
        self.prefill_path = dserve.prefill_path(
            cfg, jax.eval_shape(lambda: transformer.init_cache(cfg, 1, max_len)),
            max_prompt_len,
        )

        def _step(params, state, active):
            logits, pages2, slot2 = pool_step(
                params, state["tokens"], state["pages"], state["tables"],
                state["slot"], state["lengths"], active,
            )
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            # freeze at the slot's *reserved* capacity — the per-slot
            # analogue of the contiguous pool's max_len clamp (the
            # reservation covers plen+max_new+1, so useful tokens are
            # produced strictly before the freeze; the engine surfaces any
            # capacity-forced retirement separately)
            adv = active & (state["lengths"] < state["slot_cap"] - 1)
            return {
                **state,
                "pages": pages2,
                "slot": dserve.select_slots(active, slot2, state["slot"]),
                "tokens": jnp.where(active, nxt, state["tokens"]),
                "lengths": jnp.where(adv, state["lengths"] + 1, state["lengths"]),
            }

        self.device_step = _step

        def _admit(params, state, prompt, plen, slot, table_row, write_mask,
                   cap):
            last_logits, pages, tables, slot_leaves = slot_prefill(
                params, prompt, plen, state["pages"], state["tables"],
                state["slot"], slot, table_row, write_mask,
            )
            tok0 = jnp.argmax(last_logits, -1).astype(jnp.int32)
            return {
                "pages": pages,
                "tables": tables,
                "slot": slot_leaves,
                "tokens": state["tokens"].at[slot].set(tok0),
                "lengths": state["lengths"].at[slot].set(plen),
                "slot_cap": state["slot_cap"].at[slot].set(cap),
            }

        self._jadmit = jax.jit(_admit)
        self.reset()

    def reset(self):
        from jax.sharding import NamedSharding, PartitionSpec

        self.allocator = BlockAllocator(self.num_blocks, self.block_size)
        self.slot_blocks: List[List[int]] = [[] for _ in range(self.slots)]
        self.prefix_saved_blocks = 0  # running count of share hits
        with self.mesh:
            pages = dserve.init_paged_pool(
                self.cfg, self.max_len, self.num_blocks, self.block_size
            )
            _, slot_leaves = dserve.split_paged_cache(
                transformer.init_cache(self.cfg, self.slots, self.max_len)
            )
        # commit everything to its sharding up front (same jit-cache
        # discipline as DecodePool.reset)
        pspecs = dserve.paged_pool_specs(self.cfg, self.rules, pages)
        pages = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            pages, pspecs,
        )
        sspecs = dserve.cache_specs(self.cfg, self.rules, slot_leaves)
        slot_leaves = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            slot_leaves, sspecs,
        )
        rep = NamedSharding(self.mesh, PartitionSpec())
        zi32 = lambda *sh: jax.device_put(jnp.zeros(sh, jnp.int32), rep)  # noqa: E731
        self.state = {
            "pages": pages,
            "slot": slot_leaves,
            "tables": zi32(self.slots, self.blocks_per_slot),
            "tokens": zi32(self.slots),
            "lengths": zi32(self.slots),
            "slot_cap": zi32(self.slots),
        }

    # -- host-side block planning -------------------------------------------

    def _blocks_needed(self, plen: int, max_new: int) -> int:
        need = min(self.max_len, plen + max_new + 1)
        return -(-need // self.block_size)

    def _plan_blocks(self, prompt: np.ndarray, plen: int, max_new: int):
        """Map a request onto physical blocks.

        Full prompt blocks are content-addressed by their *cumulative*
        prefix (``prompt[:(j+1)*bs]``), so two requests with the same
        system prompt adopt the same physical blocks.  Any block the
        request may write (``j >= plen // bs``) passes through the
        copy-on-write guard — with full-prefix sharing those are private by
        construction, but the fork is the invariant that keeps a broadened
        sharing policy safe.  Rolls back cleanly on exhaustion.
        """
        bs = self.block_size
        n_need = self._blocks_needed(plen, max_new)
        first_write = plen // bs
        blocks: List[int] = []
        write_mask: List[bool] = []
        shared = 0
        try:
            for j in range(n_need):
                if self.share_prefixes and j < first_write:
                    key = prompt[: (j + 1) * bs].tobytes()
                    bid = self.allocator.lookup(key)
                    if bid is not None:
                        blocks.append(bid)
                        write_mask.append(False)
                        shared += 1
                        continue
                    bid = self.allocator.alloc()
                    self.allocator.register(key, bid)
                else:
                    bid = self.allocator.alloc()
                if j >= first_write:
                    bid, _ = self.allocator.fork_private(bid)
                blocks.append(bid)
                write_mask.append(True)
        except MemoryError:
            for b in blocks:
                self.allocator.release(b)
            raise
        return blocks, write_mask, shared

    def can_admit(self, prompt, max_new: int) -> bool:
        """Would :meth:`admit` succeed right now without evicting anyone?"""
        prompt = np.asarray(prompt, np.int32)
        plen = int(prompt.shape[0])
        n_need = self._blocks_needed(plen, max_new)
        if n_need > self.num_blocks - 1:
            raise ValueError(
                f"request needs {n_need} blocks but the pool only has "
                f"{self.num_blocks - 1} — it can never be admitted"
            )
        hits = 0
        if self.share_prefixes:
            bs = self.block_size
            for j in range(plen // bs):
                if self.allocator.peek(prompt[: (j + 1) * bs].tobytes()) is not None:
                    hits += 1
        return self.allocator.free_blocks >= n_need - hits

    # -- admission / retirement ---------------------------------------------

    def admit(self, params, prompt, slot: int, *, max_new: int) -> int:
        """Plan blocks for the request's whole budget, offset-prefill the
        prompt through the slot's new block table, return the first token."""
        prompt = np.asarray(prompt, np.int32)
        plen = int(prompt.shape[0])
        if not 0 < plen <= self.max_prompt_len:
            raise ValueError(
                f"prompt length {plen} not in (0, {self.max_prompt_len}]"
            )
        if self.slot_blocks[slot]:
            self.release_slot(slot)  # defensive: engine releases at retire
        with obs.span("paged.plan"):
            blocks, write_mask, shared = self._plan_blocks(
                prompt, plen, int(max_new)
            )
            self.slot_blocks[slot] = blocks
            self.prefix_saved_blocks += shared
            table_row = np.zeros((self.blocks_per_slot,), np.int32)
            table_row[: len(blocks)] = blocks
            mask = np.zeros((self.blocks_per_slot,), bool)
            mask[: len(blocks)] = write_mask
            padded = np.zeros((self.max_prompt_len,), np.int32)
            padded[:plen] = prompt
        # the dispatch and its first token
        with obs.span("paged.prefill", path=self.prefill_path):
            with self.mesh:
                self.state = self._jadmit(
                    params, self.state, jnp.asarray(padded), jnp.int32(plen),
                    jnp.int32(slot), jnp.asarray(table_row), jnp.asarray(mask),
                    jnp.int32(len(blocks) * self.block_size),
                )
            return int(self.state["tokens"][slot])

    def release_slot(self, slot: int) -> None:
        """Return the slot's blocks to the allocator (slot recycling)."""
        for b in self.slot_blocks[slot]:
            self.allocator.release(b)
        self.slot_blocks[slot] = []

    # -- elastic resize (DESIGN.md S15) --------------------------------------

    def export_state(self) -> Dict[str, np.ndarray]:
        """Host-side pool control state as flat arrays (broadcastable to a
        joining replica next to the device state): allocator refcounts +
        free-list order + prefix registry, and the per-slot block lists
        packed as (flat ids, per-slot lengths)."""
        flat = [b for bl in self.slot_blocks for b in bl]
        return {
            "allocator": self.allocator.export_arrays(),
            "slot_blocks": np.asarray(flat, np.int32),
            "slot_lens": np.asarray(
                [len(bl) for bl in self.slot_blocks], np.int32
            ),
            "prefix_saved": np.asarray(self.prefix_saved_blocks, np.int32),
        }

    def import_state(self, st: Dict[str, np.ndarray]) -> None:
        """Adopt a broadcast :meth:`export_state` tree (the joiner's half
        of a grow — the device ``state`` arrives separately)."""
        self.allocator = BlockAllocator.from_arrays(
            st["allocator"], self.num_blocks, self.block_size
        )
        flat = [int(b) for b in np.asarray(st["slot_blocks"])]
        out, off = [], 0
        for ln in np.asarray(st["slot_lens"]):
            out.append(flat[off : off + int(ln)])
            off += int(ln)
        self.slot_blocks = out
        self.prefix_saved_blocks = int(st["prefix_saved"])

    # -- introspection -------------------------------------------------------

    def capacity_mask(self, state):
        """Traced: slots frozen at their reserved capacity."""
        return state["lengths"] >= state["slot_cap"] - 1

    @property
    def cache_bytes(self) -> int:
        return int(
            sum(l.nbytes for l in jax.tree.leaves(self.state["pages"]))
            + sum(l.nbytes for l in jax.tree.leaves(self.state["slot"]))
            + self.state["tables"].nbytes
        )
