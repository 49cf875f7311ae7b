"""The bucketed, pipelined collective execution engine (DESIGN.md S10):
bucketizer layout invariants + pack/unpack round-trips (property-based),
bucketed == flat == per-leaf bit-agreement on the sim executor, and the
mixed-dtype preservation contract of ``tree_allreduce``.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.collectives import buckets, plans

# ---------------------------------------------------------------------------
# Layout invariants + pack/unpack round-trip (property-based)
# ---------------------------------------------------------------------------

_DTYPES = ["float32", "bfloat16", "int32", "float16"]

_leaf_spec = st.tuples(
    st.lists(st.integers(1, 7), min_size=0, max_size=3).map(tuple),  # shape
    st.sampled_from(_DTYPES),
)


def _make_tree(leaf_specs, stacked=None):
    """Deterministic, exactly-representable values (small ints) so round-
    trips can be checked bit-exactly in every dtype."""
    tree = {}
    for i, (shape, dtype) in enumerate(leaf_specs):
        full = ((stacked,) if stacked else ()) + shape
        n = int(np.prod(full)) if full else 1
        vals = (np.arange(n) % 120).reshape(full)
        tree[f"leaf{i}"] = jnp.asarray(vals).astype(dtype)
    return tree


def _check_layout(layout, leaf_specs, bucket_bytes, quantum):
    slots_seen = sorted(s.index for b in layout.buckets for s in b.slots)
    assert slots_seen == list(range(len(leaf_specs)))  # partition, no dupes
    for b in layout.buckets:
        assert all(s.dtype == b.dtype for s in b.slots)  # dtype-homogeneous
        assert b.length % quantum == 0  # padded to the plan quantum
        assert b.length >= b.used
        offsets = [(s.offset, s.size) for s in b.slots]
        pos = 0
        for off, size in offsets:  # slots tile the bucket contiguously
            assert off == pos
            pos += size
        if bucket_bytes is not None and len(b.slots) > 1:
            # cap respected whenever the bucket holds more than one leaf
            # (a single over-cap leaf legitimately gets its own bucket)
            itemsize = jnp.dtype(b.dtype).itemsize
            assert b.used * itemsize <= bucket_bytes


@given(
    leaf_specs=st.lists(_leaf_spec, min_size=1, max_size=8),
    bucket_bytes=st.sampled_from([None, 64, 256, 4096]),
    quantum=st.sampled_from([1, 4, 256]),
)
@settings(max_examples=40, deadline=None)
def test_pack_unpack_roundtrip_property(leaf_specs, bucket_bytes, quantum):
    tree = _make_tree(leaf_specs)
    layout = buckets.build_layout(
        tree, bucket_bytes=bucket_bytes, quantum=quantum
    )
    _check_layout(layout, leaf_specs, bucket_bytes, quantum)
    bufs = buckets.pack(tree, layout)
    assert [b.shape for b in bufs] == [(bk.length,) for bk in layout.buckets]
    assert [b.dtype for b in bufs] == [
        jnp.dtype(bk.dtype) for bk in layout.buckets
    ]
    out = buckets.unpack(bufs, layout)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        assert out[k].shape == tree[k].shape
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float64), np.asarray(tree[k], np.float64)
        )


@given(
    leaf_specs=st.lists(_leaf_spec, min_size=1, max_size=5),
    p=st.sampled_from([2, 3, 5]),
    bucket_bytes=st.sampled_from([None, 128]),
)
@settings(max_examples=20, deadline=None)
def test_pack_unpack_roundtrip_stacked_property(leaf_specs, p, bucket_bytes):
    """Sim trees carry a leading [p, ...] rank axis; buffers become [p, n]."""
    tree = _make_tree(leaf_specs, stacked=p)
    layout = buckets.build_layout(
        tree, bucket_bytes=bucket_bytes, quantum=2, stacked=p
    )
    bufs = buckets.pack(tree, layout)
    assert all(b.shape[0] == p for b in bufs)
    out = buckets.unpack(bufs, layout)
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float64), np.asarray(tree[k], np.float64)
        )


def test_layout_is_deterministic_and_reusable():
    tree = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((17,)), "c": jnp.zeros((2,))}
    l1 = buckets.build_layout(tree, bucket_bytes=64, quantum=4)
    l2 = buckets.build_layout(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree),
        bucket_bytes=64,
        quantum=4,
    )
    assert l1.buckets == l2.buckets  # arrays vs shape-structs: same layout
    assert l1.total_padded == sum(l1.bucket_lengths)


def test_pack_rejects_mismatched_dtype_and_structure():
    tree = {"a": jnp.zeros((4,), jnp.float32)}
    layout = buckets.build_layout(tree)
    with pytest.raises(ValueError, match="never promote"):
        buckets.pack({"a": jnp.zeros((4,), jnp.bfloat16)}, layout)
    with pytest.raises(ValueError, match="structure"):
        buckets.pack({"zz": jnp.zeros((4,), jnp.float32)}, layout)


def test_build_layout_rejects_bad_stacked_and_quantum():
    with pytest.raises(ValueError, match="rank axis"):
        buckets.build_layout({"a": jnp.zeros((3, 2))}, stacked=4)
    with pytest.raises(ValueError, match="quantum"):
        buckets.build_layout({"a": jnp.zeros((3,))}, quantum=0)


# ---------------------------------------------------------------------------
# Bucketed == flat == per-leaf bit-agreement (sim executor, identity)
# ---------------------------------------------------------------------------


def _grad_tree(p, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "wq": jnp.asarray(rng.standard_normal((p, 7, 3)), jnp.float32),
        "wk": jnp.asarray(rng.standard_normal((p, 11)), jnp.float32),
        "mlp": [
            jnp.asarray(rng.standard_normal((p, 5)), jnp.float32),
            jnp.asarray(rng.standard_normal((p, 64)), jnp.float32),
        ],
    }


def _flat_rows(tree, p):
    return jnp.concatenate(
        [l.reshape(p, -1) for l in jax.tree.leaves(tree)], axis=1
    )


@pytest.mark.parametrize("p", [2, 3, 4, 5, 6, 7, 8, 9])
@pytest.mark.parametrize("schedule", ["mrd", "rabenseifner"])
def test_bucketed_equals_flat_equals_per_leaf(p, schedule):
    """The acceptance contract: run_bucketed is bit-identical to run() on
    the flat vector (identity transform), for non-power-of-two p too, at
    every bucket granularity; the per-leaf path agrees bit-for-bit."""
    tree = _grad_tree(p, seed=p)
    plan = plans.allreduce_plan(schedule=schedule, p=p, op="sum")
    flat = _flat_rows(tree, p)
    pad = (-flat.shape[1]) % plan.pad_quantum()
    ref = plan.run(jnp.pad(flat, ((0, 0), (0, pad))))[:, : flat.shape[1]]
    for bucket_bytes in [None, 4, 40 * 4, 10**9]:
        out = plan.run_bucketed(tree, bucket_bytes=bucket_bytes)
        np.testing.assert_array_equal(
            np.asarray(_flat_rows(out, p)), np.asarray(ref)
        )
    if schedule == "mrd":  # per-leaf path: plan.run tree-maps over leaves
        per_leaf = plan.run(tree)
        np.testing.assert_array_equal(
            np.asarray(_flat_rows(per_leaf, p)), np.asarray(ref)
        )


@pytest.mark.parametrize("p", [3, 5, 8])
def test_run_buffers_matches_run_per_buffer(p):
    """run_buffers pipelines across buffers but must equal per-buffer run()
    bit-for-bit (identity transform), including RS/AG phase plans."""
    rng = np.random.default_rng(p)
    for factory, kw in [
        (plans.allreduce_plan, {"schedule": "mrd"}),
        (plans.allreduce_plan, {"schedule": "rabenseifner"}),
        (plans.reduce_scatter_plan, {}),
    ]:
        plan = factory(p=p, op="sum", **kw)
        q = plan.pad_quantum()
        bufs = [
            jnp.asarray(rng.standard_normal((p, q * k)), jnp.float32)
            for k in (1, 3, 2)
        ]
        out = plan.run_buffers(bufs)
        for b_in, b_out in zip(bufs, out):
            np.testing.assert_array_equal(
                np.asarray(b_out), np.asarray(plan.run(b_in))
            )


def test_run_buffers_validates_rs_divisibility():
    plan = plans.allreduce_plan(schedule="rabenseifner", p=4)
    with pytest.raises(ValueError, match="pad_quantum"):
        plan.run_buffers([jnp.zeros((4, 6), jnp.float32)])


def test_run_bucketed_rejects_primitive_plans():
    with pytest.raises(ValueError, match="allreduce-schedule"):
        plans.reduce_scatter_plan(p=4).run_bucketed({"a": jnp.zeros((4, 8))})


# ---------------------------------------------------------------------------
# Mixed-dtype preservation (the tree_allreduce promotion hazard, fixed)
# ---------------------------------------------------------------------------


def test_tree_allreduce_preserves_mixed_dtypes():
    """A bf16+fp32 tree must round-trip with original dtypes end-to-end —
    the old flat-ravel path promoted bf16 leaves to fp32 on the wire."""
    p = 6
    rng = np.random.default_rng(0)
    # small-integer payloads are exactly representable in every dtype, so
    # the reduced values can be compared bit-exactly regardless of the
    # schedule's reduction order
    tree = {
        "bf16": jnp.asarray(rng.integers(-8, 8, (p, 24)), jnp.bfloat16),
        "fp32": jnp.asarray(rng.integers(-64, 64, (p, 10)), jnp.float32),
        "fp16": jnp.asarray(rng.integers(-8, 8, (p, 5)), jnp.float16),
    }
    for bucket_bytes in [None, 64]:
        out = plans.tree_allreduce(tree, p=p, bucket_bytes=bucket_bytes)
        assert out["bf16"].dtype == jnp.bfloat16
        assert out["fp32"].dtype == jnp.float32
        assert out["fp16"].dtype == jnp.float16
        # small-integer payloads are exact in every dtype: check the sums
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(out[k], np.float64),
                np.broadcast_to(
                    np.asarray(tree[k], np.float64).sum(0), tree[k].shape
                ),
            )


def test_tree_allreduce_single_rank_is_noop():
    """p=1 (degenerate domain): bucketed round-trip is the identity."""
    tree = {"a": jnp.arange(6.0, dtype=jnp.float32).reshape(1, 6)}
    out = plans.tree_allreduce(tree, p=1)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))


# ---------------------------------------------------------------------------
# ZeRO-1 bucketed shard layout helpers
# ---------------------------------------------------------------------------


def test_zero1_masters_match_bucketed_layout():
    """Master rows = per-bucket owned segments concatenated in bucket
    order; non-pivot ranks of a non-power-of-two domain hold zeros."""
    from repro.distributed.gradsync.mrd_zero1 import (
        zero1_layout,
        zero1_masters_from_params,
        zero1_owner_segments,
    )

    mesh = types.SimpleNamespace(shape={"data": 3})  # dp=3, p0=2 (non-p2)
    rng = np.random.default_rng(1)
    params = {
        "a": jnp.asarray(rng.standard_normal((40, 7)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((33,)), jnp.float32),
    }
    bb = 512  # tiny cap -> several buckets
    layout, prod_p0 = zero1_layout(params, mesh, ("data",), bucket_bytes=bb)
    assert prod_p0 == 2 and len(layout.buckets) > 1
    masters = zero1_masters_from_params(params, mesh, ("data",), bucket_bytes=bb)
    assert masters.shape == (3, layout.total_padded // prod_p0)
    from repro.collectives import buckets as B

    bufs = B.pack(params, layout)
    owners = zero1_owner_segments(mesh, ("data",))
    for rank, o in enumerate(owners):
        if o is None:
            np.testing.assert_array_equal(np.asarray(masters[rank]), 0.0)
        else:
            expect = np.concatenate(
                [np.asarray(b.reshape(prod_p0, -1)[o]) for b in bufs]
            )
            np.testing.assert_array_equal(np.asarray(masters[rank]), expect)
    # paper mode: every rank replicates the concatenated padded buckets
    rep = zero1_masters_from_params(
        params, mesh, ("data",), bucket_bytes=bb, paper_mode=True
    )
    np.testing.assert_array_equal(
        np.asarray(rep[2]), np.concatenate([np.asarray(b) for b in bufs])
    )
