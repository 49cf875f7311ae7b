"""Serving step builders: decode/prefill wiring + decode-cache sharding."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of


def cache_specs(cfg: ModelConfig, rules: shd.ShardingRules, cache: Any):
    """PartitionSpecs for a decode cache pytree."""

    def spec(path, leaf):
        name = None
        for e in reversed(path):
            if isinstance(e, jax.tree_util.DictKey):
                name = str(e.key)
                break
        shape = leaf.shape
        if name in ("k", "v", "local_k", "local_v", "global_k", "global_v", "attn_k", "attn_v"):
            lead = len(shape) - 4  # [..., B, W, KV, hd]
            b = rules.batch_axes(shape[lead])
            if rules.kv_heads_sharded:
                tail = (b, None, rules.tp_axis, None)
            else:
                tail = (b, rules.tp_axis if shape[lead + 1] % rules.tp == 0 else None, None, None)
            return P(*([None] * lead), *tail)
        if name in ("k_scale", "v_scale"):  # [L, B, W, KV]
            lead = len(shape) - 3
            b = rules.batch_axes(shape[lead])
            sdim = rules.tp_axis if (not rules.kv_heads_sharded and shape[lead + 1] % rules.tp == 0) else None
            return P(*([None] * lead), b, sdim, None)
        if name == "h":  # [L, B, di, st]
            return P(None, rules.batch_axes(shape[1]), rules.tp_if(shape[2]), None)
        if name == "conv":  # [L, B, K-1, di]
            return P(None, rules.batch_axes(shape[1]), None, rules.tp_if(shape[3]))
        if name == "m_h":  # [G, k, B, nh, hp, st]
            return P(None, None, rules.batch_axes(shape[2]), rules.tp_if(shape[3]), None, None)
        if name == "m_conv":  # [G, k, B, K-1, convdim]
            return P(None, None, rules.batch_axes(shape[2]), None, None)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec, cache)


def _serve_needs_fsdp(cfg: ModelConfig, mesh: Mesh) -> bool:
    """bf16 weights sharded over "model" alone must fit in ~half the HBM."""
    tp = mesh.shape.get("model", 1)
    return cfg.n_params() * 2 / tp > 8e9


def make_serve_step(cfg: ModelConfig, mesh: Mesh):
    """Decode step: (params, tokens [B], cache, cache_len) -> (logits, cache)."""
    rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))

    def serve_step(params, tokens, cache, cache_len):
        with shd.sharding_ctx(cfg, rules):
            return transformer.forward_decode(params, tokens, cache, cache_len, cfg)

    return serve_step, rules


def make_cached_prefill_step(cfg: ModelConfig, mesh: Mesh):
    """Single-dispatch prefill that fills the decode cache.

    Scans the decode step over the prompt inside one jitted program —
    replacing the launcher's historical per-token Python loop (one XLA
    dispatch *per prompt token*) with a single ``lax.scan`` dispatch.
    Token-for-token the math is the decode step's own, so the resulting
    cache and last-position logits match the per-token loop.

    Returns ``prefill_step(params, prompt [B, S], cache) ->
    (last_logits [B, V], cache)``.
    """
    serve_step, rules = make_serve_step(cfg, mesh)

    def prefill_step(params, prompt, cache):
        S = prompt.shape[1]

        def body(c, xs):
            tok, i = xs
            logits, c = serve_step(params, tok, c, i)
            return c, logits

        cache_out, logits = jax.lax.scan(
            body, cache,
            (prompt.T, jnp.arange(S, dtype=jnp.int32)),
            unroll=1,
        )
        return logits[-1], cache_out

    return prefill_step, rules


# ---------------------------------------------------------------------------
# Continuous-batching pool steps (repro.serving, DESIGN.md S13)
# ---------------------------------------------------------------------------

# Leaf-name -> batch-axis index for every cache family built by
# ``transformer.init_cache`` (the slot dimension of a decode pool).
_CACHE_BATCH_AXIS = {
    "k": 1, "v": 1, "k_scale": 1, "v_scale": 1,          # dense/moe/vlm [L,B,...]
    "global_k": 1, "global_v": 1,                        # gemma3 [G,B,...]
    "local_k": 2, "local_v": 2,                          # gemma3 [G,P,B,...]
    "h": 1, "conv": 1,                                   # ssm [L,B,...]
    "m_h": 2, "m_conv": 2,                               # hybrid [G,k,B,...]
    "attn_k": 1, "attn_v": 1,                            # hybrid [G,B,...]
}


def _leaf_name(path) -> str:
    for e in reversed(path):
        if isinstance(e, jax.tree_util.DictKey):
            return str(e.key)
    raise KeyError(f"no dict key in path {path}")


def cache_batch_axes(cache: Any):
    """Pytree matching ``cache`` whose leaves are the batch (slot) axis index."""
    return jax.tree_util.tree_map_with_path(
        lambda p, _: _CACHE_BATCH_AXIS[_leaf_name(p)], cache
    )


def select_slots(mask, cache_new: Any, cache_old: Any):
    """Per-slot select between two caches: slot ``s`` takes ``cache_new``
    where ``mask[s]``, else ``cache_old`` (leaves keep their layout)."""

    def sel(path, new, old):
        ax = _CACHE_BATCH_AXIS[_leaf_name(path)]
        shape = [1] * new.ndim
        shape[ax] = mask.shape[0]
        return jnp.where(mask.reshape(shape), new, old)

    return jax.tree_util.tree_map_with_path(sel, cache_new, cache_old)


def _expand_slot(cache: Any):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: jnp.expand_dims(l, _CACHE_BATCH_AXIS[_leaf_name(p)]), cache
    )


def _squeeze_slot(cache: Any):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: jnp.squeeze(l, _CACHE_BATCH_AXIS[_leaf_name(p)]), cache
    )


def make_pool_decode_step(cfg: ModelConfig, mesh: Mesh):
    """Decode step with a *per-slot* cache length (continuous batching).

    ``pool_step(params, tokens [S], cache, lengths [S]) -> (logits [S,V],
    cache)`` — a ``vmap`` of the single-sequence decode step over the slot
    dimension, so every slot advances at its own position/write offset.
    Slot math is independent (vmap adds no cross-slot terms), which is what
    makes continuous batching bit-equal to solo decode per request.
    """
    rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))

    def pool_step(params, tokens, cache, lengths):
        axes = cache_batch_axes(cache)

        def one(tok, cslot, length):
            logits, c2 = transformer.forward_decode(
                params, tok[None], _expand_slot(cslot), length, cfg
            )
            return logits[0], _squeeze_slot(c2)

        with shd.sharding_ctx(cfg, rules):
            return jax.vmap(one, in_axes=(0, axes, 0), out_axes=(0, axes))(
                tokens, cache, lengths
            )

    return pool_step, rules


def prefill_path(cfg: ModelConfig, cache: Any, max_prompt_len: int) -> str:
    """How a prompt is admitted into a one-slot cache view: ``"one_pass"``
    or ``"scan"`` (``cache`` may hold arrays or ``ShapeDtypeStruct``s).

    One causal pass (:func:`transformer.forward_prefill`) yields exactly the
    rows the decode step would write when the view is plain ``k``/``v`` of a
    dense stack that holds the whole padded prompt.  Every other cache keeps
    the per-position decode scan: moe routing drops tokens over capacity per
    group, so a prompt-wide pass routes differently; ssm and hybrid state is
    recurrent; gemma3's local layers and windows shorter than the prompt
    write modulo the window; int8 K/V is attended already quantized.
    """
    one = (
        cfg.family == "dense" and not cfg.pattern_local
        and set(cache) == {"k", "v"} and cache["k"].shape[2] >= max_prompt_len
    )
    return "one_pass" if one else "scan"


def _prefill_slot(cfg, rules, params, prompt, plen, cslot, max_prompt_len: int):
    """Prefill a padded prompt into a zeroed one-slot cache view.

    Shared by the contiguous and paged offset-prefill builders so both
    admission paths trace the exact same jaxpr (bit-exactness discipline).
    Returns (cslot, last-live-position logits [V]).  The path is
    :func:`prefill_path`'s: dense stacks take one causal forward pass, whose
    K/V rows land at positions ``[0, max_prompt_len)`` (zeros from ``plen``
    on); every other family scans the decode step over the padded prompt,
    positions ``>= plen`` run but masked out of the carried cache, and
    latches the last live position's logits.
    """
    prompt = prompt[:max_prompt_len]
    if prefill_path(cfg, cslot, max_prompt_len) == "one_pass":
        with shd.sharding_ctx(cfg, rules):
            last_logits, kv = transformer.forward_prefill(
                params, prompt[None], plen, cfg
            )
        cslot = {
            n: jax.lax.dynamic_update_slice_in_dim(
                cslot[n], kv[n].astype(cslot[n].dtype), 0, axis=2
            )
            for n in cslot
        }
        return cslot, last_logits[0]

    def body(carry, xs):
        c, last = carry
        tok, i = xs
        with shd.sharding_ctx(cfg, rules):
            logits, c2 = transformer.forward_decode(
                params, tok[None], c, i, cfg
            )
        live = i < plen
        c = jax.tree.map(lambda a, b: jnp.where(live, a, b), c2, c)
        last = jnp.where(i == plen - 1, logits[0], last)
        return (c, last), None

    (cslot, last_logits), _ = jax.lax.scan(
        body,
        (cslot, jnp.zeros((cfg.vocab,), jnp.float32)),
        (prompt, jnp.arange(max_prompt_len, dtype=jnp.int32)),
        unroll=1,
    )
    return cslot, last_logits


def make_slot_prefill_step(cfg: ModelConfig, mesh: Mesh, max_prompt_len: int):
    """Offset-prefill into a live cache slot (slot recycling).

    ``slot_prefill(params, prompt [Lmax], plen, cache, slot) ->
    (last_logits [V], cache)``: the retired slot's cache slice is zeroed
    (recurrent SSM/conv state must not leak between requests; attention
    positions beyond the new length are masked anyway) and
    :func:`_prefill_slot` fills it: one causal forward pass for dense
    stacks, the decode step scanned over the padded prompt for moe, ssm,
    hybrid, gemma3 and int8 K/V (:func:`prefill_path`).  One jitted
    dispatch per admission, shapes fixed by ``max_prompt_len``, so
    admission never recompiles.  The rest of the pool is untouched, so live
    slots keep decoding across admissions.
    """
    rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))

    def slot_prefill(params, prompt, plen, cache, slot):
        cslot = jax.tree_util.tree_map_with_path(
            lambda p, l: jnp.zeros_like(
                jax.lax.dynamic_index_in_dim(
                    l, slot, axis=_CACHE_BATCH_AXIS[_leaf_name(p)], keepdims=True
                )
            ),
            cache,
        )
        cslot, last_logits = _prefill_slot(
            cfg, rules, params, prompt, plen, cslot, max_prompt_len
        )
        cache = jax.tree_util.tree_map_with_path(
            lambda p, l, s: jax.lax.dynamic_update_index_in_dim(
                l, jnp.squeeze(s, _CACHE_BATCH_AXIS[_leaf_name(p)]), slot,
                axis=_CACHE_BATCH_AXIS[_leaf_name(p)],
            ),
            cache, cslot,
        )
        return last_logits, cache

    return slot_prefill, rules


# ---------------------------------------------------------------------------
# Block-paged pool steps (repro.serving.paged, DESIGN.md S14)
# ---------------------------------------------------------------------------

# Cache leaves whose (slot, seq) slab is paged into fixed-size blocks of a
# shared physical pool.  Everything else ("slot leaves": recurrent SSM/conv
# state, rolling local windows) stays per-slot.  Every paged leaf has batch
# (slot) axis 1 and sequence axis 2 in its contiguous layout.
PAGED_LEAVES = ("k", "v", "k_scale", "v_scale", "attn_k", "attn_v")


def split_paged_cache(cache):
    """Split a decode cache dict into (paged leaves, per-slot leaves)."""
    paged = {n: l for n, l in cache.items() if n in PAGED_LEAVES}
    slot = {n: l for n, l in cache.items() if n not in PAGED_LEAVES}
    return paged, slot


def validate_pageable(cfg: ModelConfig, max_len: int) -> None:
    """Raise unless this config's decode cache can be block-paged.

    Pageable: dense/moe/vlm full-attention caches (no rolling sliding
    window — a modular write index breaks the position->block mapping) and
    hybrid attention caches (the Mamba h/conv state stays per-slot).
    """
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.pattern_local:
            raise ValueError(
                f"{cfg.name}: local-attention layers write their cache "
                "modulo the window; rolling windows are not pageable — "
                "use the contiguous pool"
            )
        if cfg.sliding_window and cfg.sliding_window < max_len:
            raise ValueError(
                f"{cfg.name}: sliding_window={cfg.sliding_window} < "
                f"max_len={max_len} writes the cache modulo the window; "
                "rolling windows are not pageable — use the contiguous pool"
            )
        return
    if cfg.family == "hybrid":
        return
    raise ValueError(
        f"family {cfg.family!r} has no pageable KV cache (recurrent state "
        "is O(1) per slot already) — use the contiguous pool"
    )


def init_paged_pool(cfg: ModelConfig, max_len: int, num_blocks: int,
                    block_size: int):
    """Physical block pools for every paged cache leaf.

    A contiguous leaf ``[D0, B, W, *tail]`` becomes a pool
    ``[D0, num_blocks, block_size, *tail]`` shared by all slots; per-slot
    block tables map logical block ``j`` (positions ``[j*bs, (j+1)*bs)``)
    to a physical block.  Block 0 is reserved as the *trash* block —
    device-side writes for inactive/masked slots are redirected there so
    the fused tick never branches on host allocator state.
    """
    validate_pageable(cfg, max_len)
    tmpl, _ = split_paged_cache(transformer.init_cache(cfg, 1, max_len))
    pool = {}
    for n, l in tmpl.items():
        if l.shape[2] != max_len:
            raise ValueError(f"paged leaf {n}: seq dim {l.shape[2]} != max_len")
        pool[n] = jnp.zeros(
            l.shape[:1] + (num_blocks, block_size) + l.shape[3:], l.dtype
        )
    return pool


def paged_pool_specs(cfg: ModelConfig, rules: shd.ShardingRules, pool: Any):
    """PartitionSpecs for a paged block pool.

    Head/hd tail axes shard exactly like the contiguous cache leaf; the
    (num_blocks, block_size) axes are replicated — blocks must move between
    slots without resharding.
    """

    def spec(path, leaf):
        name = _leaf_name(path)
        if name in ("k_scale", "v_scale"):  # [L, N, bs, KV]
            sdim = (
                rules.tp_axis
                if (not rules.kv_heads_sharded and leaf.shape[3] % rules.tp == 0)
                else None
            )
            return P(None, None, None, sdim)
        # [D0, N, bs, KV, hd]
        if rules.kv_heads_sharded:
            return P(None, None, None, rules.tp_axis, None)
        return P(
            None, None, None,
            rules.tp_axis if leaf.shape[3] % rules.tp == 0 else None, None,
        )

    return jax.tree_util.tree_map_with_path(spec, pool)


def gather_block_views(pool_leaf, tables):
    """Assemble per-slot contiguous views through the block tables.

    ``pool_leaf [D0, N, bs, *tail]`` + ``tables [S, nb]`` ->
    ``[D0, S, nb*bs, *tail]`` — exactly the contiguous cache layout, so the
    unchanged per-slot decode vmap consumes it and its math (shapes,
    reduction orders) is bit-identical to the contiguous pool step.
    Positions beyond a slot's allocation read the trash block; attention
    masks them with NEG_INF before the softmax max, so they contribute an
    exact 0.0 either way.
    """
    g = jnp.take(pool_leaf, tables, axis=1)  # [D0, S, nb, bs, *tail]
    return g.reshape(
        g.shape[0], g.shape[1], g.shape[2] * g.shape[3], *g.shape[4:]
    )


def make_paged_pool_decode_step(cfg: ModelConfig, mesh: Mesh, block_size: int,
                                attn: str = "gather"):
    """Paged decode step: gather views -> contiguous pool step -> row scatter.

    ``pool_step(params, tokens [S], pages, tables [S,nb], slot_state,
    lengths [S], write_ok [S]) -> (logits [S,V], pages, slot_state)``.

    ``attn="gather"`` (default) runs the *unchanged* contiguous per-slot
    decode vmap over block-table-gathered views — bit-exact with the
    contiguous pool by construction — then scatters the single written row
    per slot back into its physical (block, offset).  ``attn="pallas"``
    dispatches :func:`repro.models.transformer.forward_decode_paged`, which
    reads K/V through the block table *inside* the Pallas paged-attention
    kernel (no materialized views; the TPU hot path).  ``write_ok`` masks
    slots whose write is redirected to the trash block (inactive slots stay
    one fused dispatch without host branching).
    """
    if attn == "pallas":
        rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))

        def pool_step_pallas(params, tokens, pages, tables, slot_state,
                             lengths, write_ok):
            with shd.sharding_ctx(cfg, rules):
                return transformer.forward_decode_paged(
                    params, tokens, pages, tables, slot_state, lengths, cfg,
                    block_size=block_size, write_ok=write_ok,
                )

        return pool_step_pallas, rules
    if attn != "gather":
        raise ValueError(f"attn must be 'gather' or 'pallas', got {attn!r}")

    contiguous_step, rules = make_pool_decode_step(cfg, mesh)

    def pool_step(params, tokens, pages, tables, slot_state, lengths, write_ok):
        view = {n: gather_block_views(pages[n], tables) for n in pages}
        logits, cache2 = contiguous_step(
            params, tokens, {**view, **slot_state}, lengths
        )
        # physical (block, offset) of the one row each slot wrote; masked
        # slots land in the reserved trash block 0
        pb = jnp.take_along_axis(
            tables, (lengths // block_size)[:, None], axis=1
        )[:, 0]
        pb = jnp.where(write_ok, pb, 0)
        off = jnp.where(write_ok, lengths % block_size, 0)
        pages2 = {}
        for n in pages:
            idx = lengths.reshape((1, -1, 1) + (1,) * (cache2[n].ndim - 3))
            row = jnp.squeeze(
                jnp.take_along_axis(cache2[n], idx, axis=2), 2
            )  # [D0, S, *tail]
            pages2[n] = pages[n].at[:, pb, off].set(row)
        slot2 = {n: cache2[n] for n in slot_state}
        return logits, pages2, slot2

    return pool_step, rules


def make_paged_slot_prefill_step(cfg: ModelConfig, mesh: Mesh,
                                 max_prompt_len: int, max_len: int,
                                 block_size: int):
    """Offset-prefill a prompt into a slot's *block table* (paged admission).

    ``slot_prefill(params, prompt [Lmax], plen, pages, tables, slot_state,
    slot, table_row [nb], write_mask [nb]) -> (last_logits [V], pages,
    tables, slot_state)``.

    Runs the shared :func:`_prefill_slot` over a zeroed full-length view
    (same jaxpr as the contiguous admission — bit-exactness): one causal
    forward pass for dense stacks, the per-position decode scan for moe,
    hybrid and int8 K/V (:func:`prefill_path`).  It then scatters
    whole blocks into the physical pool: logical block ``j`` goes to
    ``table_row[j]`` where ``write_mask[j]``, else to the trash block —
    shared prefix blocks are *skip-written* (their recomputed content is
    bit-identical by determinism; the registered copy stays untouched).
    Shapes are fixed by ``max_prompt_len``/``nb``, so admission never
    recompiles.
    """
    rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))
    tmpl, _ = split_paged_cache(transformer.init_cache(cfg, 1, max_len))
    shapes = {n: (l.shape, l.dtype) for n, l in tmpl.items()}

    def slot_prefill(params, prompt, plen, pages, tables, slot_state, slot,
                     table_row, write_mask):
        view0 = {n: jnp.zeros(sh, dt) for n, (sh, dt) in shapes.items()}
        slot0 = jax.tree_util.tree_map_with_path(
            lambda p, l: jnp.zeros_like(
                jax.lax.dynamic_index_in_dim(
                    l, slot, axis=_CACHE_BATCH_AXIS[_leaf_name(p)], keepdims=True
                )
            ),
            slot_state,
        )
        cslot, last_logits = _prefill_slot(
            cfg, rules, params, prompt, plen, {**view0, **slot0},
            max_prompt_len,
        )
        nb = table_row.shape[0]
        dst = jnp.where(write_mask, table_row, 0)
        pages2 = {}
        for n in pages:
            leaf = jnp.squeeze(cslot[n], 1)  # [D0, W, *tail]
            blocks = leaf.reshape(
                leaf.shape[0], nb, block_size, *leaf.shape[2:]
            )
            pages2[n] = pages[n].at[:, dst].set(blocks)
        slot2 = jax.tree_util.tree_map_with_path(
            lambda p, l, s: jax.lax.dynamic_update_index_in_dim(
                l, jnp.squeeze(s, _CACHE_BATCH_AXIS[_leaf_name(p)]), slot,
                axis=_CACHE_BATCH_AXIS[_leaf_name(p)],
            ),
            slot_state, {n: cslot[n] for n in slot_state},
        )
        tables2 = tables.at[slot].set(table_row)
        return last_logits, pages2, tables2, slot2

    return slot_prefill, rules


def make_prefill_step(cfg: ModelConfig, mesh: Mesh):
    """Prefill: full forward, returns last-position logits [B, V]."""
    rules = shd.make_rules(cfg, mesh, fsdp=_serve_needs_fsdp(cfg, mesh))

    def prefill_step(params, batch):
        with shd.sharding_ctx(cfg, rules):
            x, _ = transformer._embed_inputs(params, batch, cfg)
            x = shd.constrain(x.astype(dtype_of(cfg.compute_dtype)), "tokens")
            S = x.shape[1]
            pos = jnp.arange(S)[None, :]
            x, _ = transformer._run_stack(params, x, cfg, pos)
            from repro.models.layers import rmsnorm

            x = rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
            return transformer._logits(params, x, cfg)[:, 0]

    return prefill_step, rules


# ---------------------------------------------------------------------------
# Elastic serving: bit-exact state transfer to joining replicas (S15)
# ---------------------------------------------------------------------------


_BCAST_JIT: dict = {}


def mrd_broadcast_stacked(tree, p: int, src: int = 0, dst: int = None):
    """Simulated-replica analogue of ``runtime.elastic.mrd_broadcast``.

    The serving engine's termination agreement runs over *stacked* replicas
    (sim-executor MRD plans over a leading ``[p]`` axis), so the grow path's
    state transfer is the same protocol move at the same extent: rank
    ``src`` contributes the real leaves, every other rank contributes exact
    zeros, and the MRD **sum**-allreduce makes ``x + 0`` bit-exact at every
    stage — the value landing on the joiner (``dst``, default the last,
    newly appended rank) equals the source's bit for bit.  Bool leaves ride
    as uint8; zero-size leaves pass through untouched.  Returns the tree as
    received by ``dst``.

    The whole tree moves through **one** jitted program (cached per
    ``(p, src, dst, structure, shapes)``): a per-leaf eager loop dispatches
    thousands of stage-sized ops for a full model tree, which would
    dominate a live grow.
    """
    from repro.collectives import plans as _plans

    if dst is None:
        dst = p - 1
    if p == 1:
        return jax.tree.map(jnp.asarray, tree)
    leaves, treedef = jax.tree.flatten(tree)
    leaves = [jnp.asarray(l) for l in leaves]
    key = (p, src, dst, treedef,
           tuple((l.shape, str(l.dtype)) for l in leaves))
    fn = _BCAST_JIT.get(key)
    if fn is None:
        plan = _plans.allreduce_plan(schedule="mrd", p=p, op="sum")

        def one(leaf):
            if leaf.size == 0:
                return leaf
            as_bool = leaf.dtype == jnp.bool_
            x = leaf.astype(jnp.uint8) if as_bool else leaf
            stacked = jnp.zeros((p,) + x.shape, x.dtype).at[src].set(x)
            out = plan.run(stacked)[dst]
            return out.astype(jnp.bool_) if as_bool else out

        fn = _BCAST_JIT[key] = jax.jit(lambda ls: [one(l) for l in ls])
    return jax.tree.unflatten(treedef, fn(leaves))
