"""Property tests for the paged-cache block allocator (DESIGN.md S14).

Model-based hypothesis tests drive :class:`repro.serving.paged.BlockAllocator`
through arbitrary alloc/release/share/fork sequences against a reference
refcount model, checking the load-bearing invariants:

- a block is never handed out twice while allocated (no double-assignment);
- a block returns to the free list exactly when its last sharer releases it;
- copy-on-write (``fork_private``) never touches a block other sharers
  still hold — the writer moves to a fresh block instead;
- the prefix registry only ever points at live blocks and is dropped with
  the last reference.

Plus example-based tests for the host-side block planner
(``PagedDecodePool._plan_blocks``): cumulative-prefix sharing, write-mask
shape, and clean rollback on exhaustion.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.paged import BlockAllocator, PagedDecodePool


# ---------------------------------------------------------------------------
# Example-based allocator behavior
# ---------------------------------------------------------------------------


def test_alloc_is_deterministic_lowest_first():
    a = BlockAllocator(6, 8)
    assert [a.alloc() for _ in range(5)] == [1, 2, 3, 4, 5]
    with pytest.raises(MemoryError):
        a.alloc()
    a.release(3)
    assert a.alloc() == 3  # immediate reuse of the freed block
    a.check()


def test_trash_block_is_pinned():
    a = BlockAllocator(4, 8)
    with pytest.raises(ValueError):
        a.release(0)
    with pytest.raises(ValueError):
        a.retain(0)
    with pytest.raises(ValueError):
        a.register(b"k", 0)
    a.check()


def test_registry_lifecycle():
    a = BlockAllocator(4, 8)
    b = a.alloc()
    a.register(b"sys", b)
    assert a.peek(b"sys") == b
    assert a.lookup(b"sys") == b  # second sharer
    assert a.ref[b] == 2
    assert not a.release(b)  # first sharer leaves: still live
    assert a.peek(b"sys") == b
    assert a.release(b)  # last sharer leaves: freed + deregistered
    assert a.peek(b"sys") is None
    assert a.free_blocks == 3
    a.check()


def test_fork_private_cow():
    a = BlockAllocator(5, 8)
    b = a.alloc()
    a.register(b"sys", b)
    a.lookup(b"sys")  # second sharer
    nb, copied = a.fork_private(b)
    assert copied and nb != b  # shared: writer moved to a fresh block
    assert a.ref[b] == 1 and a.peek(b"sys") == b  # sharer's view untouched
    nb2, copied2 = a.fork_private(nb)
    assert nb2 == nb and not copied2  # exclusive: write in place
    a.check()


def test_fork_private_oom_keeps_reference():
    a = BlockAllocator(2, 8)  # single usable block
    b = a.alloc()
    a.retain(b)  # shared, and no free block to fork into
    with pytest.raises(MemoryError):
        a.fork_private(b)
    assert a.ref[b] == 2  # the failed fork must not leak the caller's ref
    a.check()


# ---------------------------------------------------------------------------
# Model-based: arbitrary op sequences vs a reference refcount model
# ---------------------------------------------------------------------------


OPS = st.lists(
    st.tuples(st.integers(0, 4), st.integers(0, 1 << 30), st.integers(0, 7)),
    max_size=80,
)


@given(OPS, st.integers(2, 12))
@settings(max_examples=200, deadline=None)
def test_allocator_model(ops, num_blocks):
    a = BlockAllocator(num_blocks, 8)
    held = []  # every reference this "client" holds, one entry per ref
    for code, x, y in ops:
        if code == 0:  # alloc
            if a.free_blocks:
                b = a.alloc()
                assert b != 0 and b not in held  # no double-assignment
                held.append(b)
            else:
                with pytest.raises(MemoryError):
                    a.alloc()
        elif code == 1 and held:  # release one reference
            b = held.pop(x % len(held))
            freed = a.release(b)
            assert freed == (b not in held)  # freed iff last sharer left
        elif code == 2 and held:  # retain (extra sharer)
            b = held[x % len(held)]
            a.retain(b)
            held.append(b)
        elif code == 3 and held:  # register/lookup through the registry
            b = held[x % len(held)]
            key = bytes([y])
            owner = a.peek(key)
            if owner is None:
                a.register(key, b)
                owner = b
            got = a.lookup(key)
            assert got == owner
            held.append(got)
        elif code == 4 and held:  # fork_private (COW)
            b = held[x % len(held)]
            if held.count(b) == 1:
                nb, copied = a.fork_private(b)
                assert nb == b and not copied
            elif a.free_blocks:
                others = held.count(b) - 1
                held.remove(b)
                nb, copied = a.fork_private(b)
                assert copied and nb != b and nb not in held
                assert a.ref[b] == others  # sharers keep the old block
                held.append(nb)
            else:
                with pytest.raises(MemoryError):
                    a.fork_private(b)
        # cross-check the reference model and the structural invariants
        counts = np.bincount(held, minlength=num_blocks) if held else (
            np.zeros(num_blocks, np.int64)
        )
        assert (a.ref[1:] == counts[1:]).all()
        a.check()
    for b in list(held):  # drain: everything must come back
        held.remove(b)
        a.release(b)
    assert a.free_blocks == num_blocks - 1
    a.check()


# ---------------------------------------------------------------------------
# Host-side block planning (no device state needed)
# ---------------------------------------------------------------------------


def _planner(num_blocks, *, block_size=4, max_len=16, share=True):
    """A PagedDecodePool stripped to its host-side planning half."""
    p = object.__new__(PagedDecodePool)
    p.block_size = block_size
    p.max_len = max_len
    p.max_prompt_len = max_len - block_size
    p.share_prefixes = share
    p.blocks_per_slot = max_len // block_size
    p.num_blocks = num_blocks
    p.allocator = BlockAllocator(num_blocks, block_size)
    return p


def test_plan_shares_cumulative_prefix_blocks():
    p = _planner(32)
    sys_prefix = np.arange(8, dtype=np.int32)  # 2 full blocks
    pa = np.concatenate([sys_prefix, [101, 102]]).astype(np.int32)
    pb = np.concatenate([sys_prefix, [201]]).astype(np.int32)
    ba, wa, sa = p._plan_blocks(pa, len(pa), 2)
    bb, wb, sb = p._plan_blocks(pb, len(pb), 2)
    assert sa == 0 and sb == 2  # second request adopts both prefix blocks
    assert bb[:2] == ba[:2] and bb[2] not in ba
    assert wa == [True] * 4 and wb == [False, False, True]
    assert (p.allocator.ref[ba[:2]] == 2).all()
    # divergent prefix shares nothing
    pc = np.concatenate([[9] * 8, [301]]).astype(np.int32)
    bc, wc, sc = p._plan_blocks(pc, len(pc), 2)
    assert sc == 0 and not set(bc) & set(ba)
    p.allocator.check()


def test_plan_partial_block_prefix_not_shared():
    p = _planner(32)
    pa = np.arange(6, dtype=np.int32)  # 1 full block + 2 tokens
    ba, wa, _ = p._plan_blocks(pa, len(pa), 4)
    bb, wb, sb = p._plan_blocks(pa.copy(), len(pa), 4)
    assert sb == 1  # only the full block is shared
    assert bb[0] == ba[0] and bb[1] != ba[1]  # the half-written one is private
    assert wb == [False, True, True]
    p.allocator.check()


def test_plan_rolls_back_on_exhaustion():
    p = _planner(3)  # 2 usable blocks
    big = np.arange(8, dtype=np.int32)
    free0 = p.allocator.free_blocks
    with pytest.raises(MemoryError):
        p._plan_blocks(big, len(big), 8)  # needs 3 blocks, only 2 exist
    assert p.allocator.free_blocks == free0  # clean rollback
    p.allocator.check()


def test_can_admit_rejects_never_fitting_request():
    p = _planner(3, block_size=4, max_len=16)
    with pytest.raises(ValueError):
        p.can_admit(np.arange(8, dtype=np.int32), 16)  # needs 4 > 2 usable
    assert p.can_admit(np.arange(4, dtype=np.int32), 2)  # 2 blocks: fits
    p.allocator.alloc()
    # still fits in principle (2 <= 2 usable) but not right now (1 free)
    assert not p.can_admit(np.arange(4, dtype=np.int32), 2)


@given(
    st.lists(
        st.tuples(
            st.integers(0, 2),  # prompt family (shared alphabet -> collisions)
            st.integers(1, 11),  # prompt length
            st.integers(1, 6),  # max_new
        ),
        min_size=1,
        max_size=24,
    )
)
@settings(max_examples=100, deadline=None)
def test_plan_release_cycles_conserve_blocks(reqs):
    p = _planner(64, block_size=4, max_len=16)
    plans = []
    for fam, plen, max_new in reqs:
        prompt = np.full((plen,), fam, np.int32)
        try:
            blocks, mask, _ = p._plan_blocks(prompt, plen, max_new)
        except MemoryError:
            continue
        assert len(blocks) == len(mask) <= p.blocks_per_slot
        # every writable block is exclusively owned
        for b, w in zip(blocks, mask):
            if w:
                assert p.allocator.ref[b] == 1
        plans.append(blocks)
        p.allocator.check()
    for blocks in plans:
        for b in blocks:
            p.allocator.release(b)
    assert p.allocator.free_blocks == 63
    p.allocator.check()
