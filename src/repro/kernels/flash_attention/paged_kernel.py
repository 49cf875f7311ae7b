"""Pallas paged-attention decode kernel: K/V gathered through a block table.

Serving decode with a block-paged cache (DESIGN.md S14): each sequence's
K/V lives in fixed-size blocks scattered across a shared physical pool
``[num_blocks, block_size, KV, hd]``, addressed by a per-sequence block
table.  One decode query attends over its blocks by walking the table
*inside* the kernel — no gathered/contiguous copy of the cache is ever
materialized.

Grid: (S, KV) — one program per (sequence, kv-head).  The block table and
lengths ride in SMEM through scalar prefetch, so the address arithmetic
runs on the scalar unit.  The pools stay in HBM (``memory_space=ANY``);
each program copies only its live ``[block_size, hd]`` tiles into a
two-slot VMEM buffer, fetching block ``j+1`` while block ``j`` is being
reduced.  The GQA query group (rep = H // KV) rides in the sublane
dimension, so the score matrix per block is [rep, block_size] and the
online-softmax running state (m, l, acc) matches ``kernel.py``'s flash
forward exactly.  The loop bound is the *dynamic*
``ceil(length / block_size)``, so a short sequence in a long table does
proportional work and VMEM use is independent of the pool size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    bt_ref,   # SMEM [S, nb] int32     block tables
    len_ref,  # SMEM [S] int32         valid cache positions
    q_ref,    # VMEM [H, KV*hd]        block-diagonal queries of one sequence
    k_hbm,    # ANY  [N, bs, KV*hd]    physical pool (heads folded into lanes)
    v_hbm,    # ANY  [N, bs, KV*hd]
    o_ref,    # VMEM [H, KV*hd]
    k_buf,    # VMEM [2, bs, KV*hd]    double buffer
    v_buf,    # VMEM [2, bs, KV*hd]
    sem,      # DMA semaphores [2, 2] (k/v, slot)
    *,
    block_size: int,
    hd: int,
):
    s = pl.program_id(0)
    H = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * (hd**-0.5)
    length = len_ref[s]
    nblk = pl.cdiv(length, block_size)

    def copies(j, slot):
        pb = bt_ref[s, j]
        return (
            pltpu.make_async_copy(k_hbm.at[pb], k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[pb], v_buf.at[slot], sem.at[1, slot]),
        )

    @pl.when(nblk > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        m_run, l_run, acc = carry
        slot = j % 2
        for c in copies(j, slot):
            c.wait()

        @pl.when(j + 1 < nblk)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        k_blk = k_buf[slot].astype(jnp.float32)
        v_blk = v_buf[slot].astype(jnp.float32)
        # q is zero outside each head's own hd lanes, so this one matmul
        # gives every head's scores against its own kv head: [H, bs]
        sc = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (H, block_size), 1
        )
        sc = jnp.where(k_pos < length, sc, NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(sc - m_new)
        l_new = l_run * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros(o_ref.shape, jnp.float32)
    _, l_f, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l_f, 1e-30)).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths, *,
                        interpret: bool = False):
    """q: [S, H, hd]; k_pages/v_pages: [N, bs, KV, hd];
    block_tables: [S, nb] int; lengths: [S] int (valid positions per
    sequence) -> [S, H, hd]."""
    S, H, hd = q.shape
    N, bs, KV, _ = k_pages.shape
    rep = H // KV
    W = KV * hd

    # head h's queries occupy lanes [g*hd, (g+1)*hd) of the folded pool row
    # for its kv head g only; the kernel's [H, W] output keeps the same
    # diagonal, which is all that is read back
    eye = jnp.eye(KV, dtype=q.dtype)
    qbd = jnp.einsum("sgrd,gh->sgrhd", q.reshape(S, KV, rep, hd), eye)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((None, H, W), lambda s, bt, ln: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, H, W), lambda s, bt, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bs, W), k_pages.dtype),
            pltpu.VMEM((2, bs, W), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, W), q.dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        qbd.reshape(S, H, W),
        k_pages.reshape(N, bs, W),
        v_pages.reshape(N, bs, W),
    )
    out = out.reshape(S, KV, rep, KV, hd)
    return jnp.einsum("sgrhd,gh->sgrd", out, eye).reshape(S, H, hd)
