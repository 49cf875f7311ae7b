"""Unified model builder for all assigned architectures.

Structural families (one code path each, params stacked for ``lax.scan``):

- ``dense`` / ``vlm`` / ``audio``: homogeneous decoder/encoder stack
  (llama3.2, minicpm, qwen2.5, internvl2-backbone, hubert).
- ``moe``: dense stack with MoE MLPs (mixtral, llama4-scout).
- ``gemma3``: grouped stack — G groups of (pattern_local local-attention
  layers + pattern_global global layers), dual RoPE theta, dual caches.
- ``ssm``: mamba1 stack (falcon-mamba).
- ``hybrid``: G groups of ``attn_every`` mamba2 layers + ONE weight-shared
  attention/MLP block applied after each group (zamba2).

Interfaces:
  init_params(cfg, key)                              -> params
  forward_train(params, batch, cfg, remat_policy)    -> (loss, metrics)
  init_cache(cfg, batch, max_len)                    -> cache
  forward_prefill(params, tokens, plen, cfg)         -> (last_logits, {k, v})
  forward_decode(params, tok, cache, cache_len, cfg) -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import attention, attn_init, decode_attention, qkv_project
from repro.models.config import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.layers import (
    dense_init,
    dtype_of,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)

# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------


def _attn_layer_init(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "attn": attn_init(k1, cfg, dtype),
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, dtype, cfg.act)
    return p


def _stacked(init_one, keys):
    return jax.vmap(init_one)(keys)


def init_params(cfg: ModelConfig, key) -> dict[str, Any]:
    dtype = dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.vocab, cfg.d_model), dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab), dtype)

    if cfg.family in ("dense", "moe", "vlm", "audio") and not cfg.pattern_local:
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = _stacked(
            lambda k: _attn_layer_init(k, cfg, dtype), lkeys
        )
    elif cfg.pattern_local:  # gemma3 grouped local/global
        per = cfg.pattern_local + cfg.pattern_global
        G = cfg.n_layers // per
        lk = jax.random.split(keys[2], G * cfg.pattern_local).reshape(
            G, cfg.pattern_local, -1
        )
        gk = jax.random.split(keys[3], G)
        params["local_layers"] = jax.vmap(
            lambda ks: _stacked(lambda k: _attn_layer_init(k, cfg, dtype), ks)
        )(lk)
        params["global_layers"] = _stacked(
            lambda k: _attn_layer_init(k, cfg, dtype), gk
        )
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = _stacked(
            lambda k: {
                "mamba": ssm_lib.mamba1_init(k, cfg, dtype),
                "ln": rmsnorm_init(cfg.d_model, dtype),
            },
            lkeys,
        )
    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        lk = jax.random.split(keys[2], cfg.n_layers).reshape(G, cfg.attn_every, -1)
        params["mamba_groups"] = jax.vmap(
            lambda ks: _stacked(
                lambda k: {
                    "mamba": ssm_lib.mamba2_init(k, cfg, dtype),
                    "ln": rmsnorm_init(cfg.d_model, dtype),
                },
                ks,
            )
        )(lk)
        params["shared_attn"] = _attn_layer_init(keys[4], cfg, dtype)
    else:
        raise ValueError(f"unknown family {cfg.family}")

    if cfg.frontend == "vision":
        params["patch_proj"] = dense_init(keys[5], (1024, cfg.d_model), dtype)
    elif cfg.frontend == "audio":
        params["frame_proj"] = dense_init(keys[5], (80, cfg.d_model), dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks (full-sequence)
# ---------------------------------------------------------------------------


def _attn_block_kv(lp, x, cfg, positions, *, theta, window, impl):
    """Attention sub-block.  Returns (x, k, v): ``k``/``v`` are the layer's
    post-RoPE rows at ``n_kv_heads`` — what a decode cache stores."""
    from repro.distributed.sharding import kv_repeat_factor

    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(lp["attn"], h, cfg, positions, theta)
    k_att, v_att = k, v
    rep = kv_repeat_factor(cfg.n_heads, cfg.n_kv_heads)
    if rep > 1:  # make the kv head count TP-divisible (see sharding.py)
        k_att = constrain(jnp.repeat(k, rep, axis=2), "k")
        v_att = constrain(jnp.repeat(v, rep, axis=2), "v")
    o = attention(q, k_att, v_att, causal=cfg.causal, window=window, impl=impl,
                  chunk=min(cfg.attn_chunk, x.shape[1]))
    B, S = x.shape[:2]
    return constrain(x + o.reshape(B, S, -1) @ lp["attn"]["wo"], "tokens"), k, v


def _attn_block(lp, x, cfg, positions, *, theta, window, impl):
    return _attn_block_kv(lp, x, cfg, positions, theta=theta, window=window,
                          impl=impl)[0]


def _mlp_block(lp, x, cfg):
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_lib.moe_apply(lp["moe"], h, cfg)
        return x + y, aux
    return x + mlp_apply(lp["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)


def _decoder_layer(lp, x, cfg, positions, *, theta=None, window="default", impl=None):
    theta = cfg.rope_theta if theta is None else theta
    window = cfg.sliding_window if window == "default" else window
    impl = cfg.attn_impl if impl is None else impl
    x = _attn_block(lp, x, cfg, positions, theta=theta, window=window, impl=impl)
    return _mlp_block(lp, x, cfg)


def _embed_inputs(params, batch, cfg):
    """Token/frontend embedding. Returns (x [B,S,d], label_offset)."""
    if cfg.frontend == "audio":
        x = batch["frames"] @ params["frame_proj"]
        return x, 0
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.frontend == "vision" and "patches" in batch:
        vis = batch["patches"] @ params["patch_proj"]
        x = jnp.concatenate([vis, x], axis=1)
        return x, vis.shape[1]
    return x, 0


def _logits(params, x, cfg):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain((x @ head).astype(jnp.float32), "logits")


# ---------------------------------------------------------------------------
# Full forward (train / prefill share the stack traversal)
# ---------------------------------------------------------------------------


def _run_stack(params, x, cfg, positions, remat_policy=None):
    """Run the layer stack. Returns (x, aux_loss)."""

    def maybe_remat(fn):
        if remat_policy is None:
            return fn
        return jax.checkpoint(fn, policy=remat_policy)

    aux0 = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe", "vlm", "audio") and not cfg.pattern_local:

        def body(carry, lp):
            h, aux = carry
            h = _attn_block(
                lp, h, cfg, positions,
                theta=cfg.rope_theta, window=cfg.sliding_window, impl=cfg.attn_impl,
            )
            h, aux_l = _mlp_block(lp, h, cfg)
            return (h, aux + aux_l), None

        (x, aux), _ = jax.lax.scan(maybe_remat(body), (x, aux0), params["layers"], unroll=cfg.scan_unroll)
        return x, aux

    if cfg.pattern_local:  # gemma3 grouped local/global
        theta_g = cfg.rope_theta_global or cfg.rope_theta

        def local_body(carry, lp):
            h, aux = carry
            h = _attn_block(
                lp, h, cfg, positions,
                theta=cfg.rope_theta, window=cfg.local_window, impl=cfg.attn_impl,
            )
            h, aux_l = _mlp_block(lp, h, cfg)
            return (h, aux + aux_l), None

        def global_block(carry, glp):
            h, aux = carry
            h = _attn_block(
                glp, h, cfg, positions,
                theta=theta_g, window=None, impl=cfg.attn_impl,
            )
            h, aux_g = _mlp_block(glp, h, cfg)
            return (h, aux + aux_g)

        def group_body(carry, gp):
            carry, _ = jax.lax.scan(maybe_remat(local_body), carry, gp["local"], unroll=cfg.scan_unroll)
            return maybe_remat(global_block)(carry, gp["global"]), None

        groups = {"local": params["local_layers"], "global": params["global_layers"]}
        (x, aux), _ = jax.lax.scan(group_body, (x, aux0), groups, unroll=cfg.scan_unroll)
        return x, aux

    if cfg.family == "ssm":

        def body(carry, lp):
            h = carry + ssm_lib.mamba1_apply(
                lp["mamba"], rmsnorm(carry, lp["ln"], cfg.norm_eps), cfg,
                chunk=cfg.ssm_chunk,
            )
            return h, None

        x, _ = jax.lax.scan(maybe_remat(body), x, params["layers"], unroll=cfg.scan_unroll)
        return x, aux0

    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(carry, lp):
            h = carry + ssm_lib.mamba2_apply(
                lp["mamba"], rmsnorm(carry, lp["ln"], cfg.norm_eps), cfg,
                chunk=min(cfg.ssm_chunk, carry.shape[1]),
            )
            return h, None

        def shared_block(h, aux):
            h = _attn_block(
                shared, h, cfg, positions,
                theta=cfg.rope_theta, window=None, impl=cfg.attn_impl,
            )
            h, aux_g = _mlp_block(shared, h, cfg)
            return (h, aux + aux_g)

        def group_body(carry, gp):
            h, aux = carry
            h, _ = jax.lax.scan(maybe_remat(mamba_body), h, gp, unroll=cfg.scan_unroll)
            return maybe_remat(shared_block)(h, aux), None

        (x, aux), _ = jax.lax.scan(group_body, (x, aux0), params["mamba_groups"], unroll=cfg.scan_unroll)
        return x, aux

    raise ValueError(cfg.family)


def _train_head(params, x, aux, batch, cfg: ModelConfig, label_offset: int = 0):
    """Final norm + logits + CE loss on the stack output ``x``.

    Reads only ``final_norm`` and the output head (``lm_head``, or
    ``embed`` when tied) from ``params``.  Split out of
    :func:`forward_train` so the ready-bucket overlap path can take its
    VJP separately from the stack and embedding segments (DESIGN.md S16)
    while both paths share the exact same ops.
    """
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if label_offset:
        x = x[:, label_offset:]
    logits = _logits(params, x, cfg)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    # CE via vocab-axis reductions only: take_along_axis would gather across
    # the vocab-sharded logits (an all-gather of the full fp32 logits under
    # GSPMD); max/sum reductions and the iota-match partition cleanly.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    V = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    lab = jnp.sum(jnp.where(iota == safe[..., None], shifted, 0.0), axis=-1)
    ll = lab - lse
    ntok = jnp.maximum(mask.sum(), 1.0)
    loss = -(ll * mask).sum() / ntok
    per_example = -(ll * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)  # [B]
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux, "ntok": ntok, "per_example": per_example}


def forward_train(params, batch, cfg: ModelConfig, remat_policy=None):
    """Returns (loss, metrics)."""
    x, label_offset = _embed_inputs(params, batch, cfg)
    x = constrain(x.astype(dtype_of(cfg.compute_dtype)), "tokens")
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None, :]
    x, aux = _run_stack(params, x, cfg, positions, remat_policy)
    return _train_head(params, x, aux, batch, cfg, label_offset)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _kv_store_dtype(cfg):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype_of(cfg.compute_dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = _kv_store_dtype(cfg)
    hd, KV = cfg.head_dim, cfg.n_kv_heads
    if cfg.family in ("dense", "moe", "vlm") and not cfg.pattern_local:
        W = min(max_len, cfg.sliding_window or max_len)
        L = cfg.n_layers
        cache = {
            "k": jnp.zeros((L, batch, W, KV, hd), dtype),
            "v": jnp.zeros((L, batch, W, KV, hd), dtype),
        }
        if cfg.kv_cache_dtype == "int8":
            cache["k_scale"] = jnp.zeros((L, batch, W, KV), jnp.float32)
            cache["v_scale"] = jnp.zeros((L, batch, W, KV), jnp.float32)
        return cache
    if cfg.pattern_local:
        per = cfg.pattern_local + cfg.pattern_global
        G = cfg.n_layers // per
        Wl = min(max_len, cfg.local_window or max_len)
        return {
            "local_k": jnp.zeros((G, cfg.pattern_local, batch, Wl, KV, hd), dtype),
            "local_v": jnp.zeros((G, cfg.pattern_local, batch, Wl, KV, hd), dtype),
            "global_k": jnp.zeros((G, batch, max_len, KV, hd), dtype),
            "global_v": jnp.zeros((G, batch, max_len, KV, hd), dtype),
        }
    if cfg.family == "ssm":
        L = cfg.n_layers
        return {
            "h": jnp.zeros((L, batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
        }
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        k = cfg.attn_every
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        return {
            "m_h": jnp.zeros(
                (G, k, batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
            ),
            "m_conv": jnp.zeros((G, k, batch, cfg.ssm_conv - 1, conv_dim), dtype),
            "attn_k": jnp.zeros((G, batch, max_len, KV, hd), dtype),
            "attn_v": jnp.zeros((G, batch, max_len, KV, hd), dtype),
        }
    raise ValueError(f"no cache for family {cfg.family}")


def _quant_heads(x):
    """x: [B,1,KV,hd] -> (int8 [B,1,KV,hd], scale f32 [B,1,KV])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _write_cache(kc, vc, k_new, v_new, cache_len, window: int | None,
                 ks=None, vs=None):
    """kc/vc: [B,W,KV,hd]; k_new/v_new: [B,1,KV,hd]. Rolling write for windows.
    int8 caches also update the per-(token, head) scale planes (ks/vs)."""
    W = kc.shape[1]
    idx = cache_len % W if window is not None else cache_len
    if kc.dtype == jnp.int8:
        k_q, k_s = _quant_heads(k_new)
        v_q, v_s = _quant_heads(v_new)
        kc = jax.lax.dynamic_update_slice(kc, k_q, (0, idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v_q, (0, idx, 0, 0))
        ks = jax.lax.dynamic_update_slice(ks, k_s, (0, idx, 0))
        vs = jax.lax.dynamic_update_slice(vs, v_s, (0, idx, 0))
    else:
        kc = constrain(jax.lax.dynamic_update_slice(kc, k_new, (0, idx, 0, 0)), "cache_k")
        vc = constrain(jax.lax.dynamic_update_slice(vc, v_new, (0, idx, 0, 0)), "cache_v")
    valid = jnp.minimum(cache_len + 1, W)
    return kc, vc, ks, vs, valid


def _dequant_cache(c, s, out_dtype):
    """c: int8 [B,W,KV,hd]; s: f32 [B,W,KV] -> [B,W,KV,hd] out_dtype."""
    return (c.astype(jnp.float32) * s[..., None]).astype(out_dtype)


def _decode_attn_layer(lp, x_tok, kc, vc, cache_len, cfg, *, theta, window,
                       ks=None, vs=None):
    """x_tok: [B,d]. Returns (x, kc, vc[, ks, vs])."""
    B = x_tok.shape[0]
    h = rmsnorm(x_tok[:, None], lp["ln1"], cfg.norm_eps)
    pos = jnp.full((B, 1), cache_len, jnp.int32)
    q, k, v = qkv_project(lp["attn"], h, cfg, pos, theta)
    kc, vc, ks, vs, valid = _write_cache(kc, vc, k, v, cache_len, window, ks, vs)
    cdt = dtype_of(cfg.compute_dtype)
    if kc.dtype == jnp.int8:
        k_at = _dequant_cache(kc, ks, cdt)
        v_at = _dequant_cache(vc, vs, cdt)
    else:
        k_at, v_at = kc, vc
    o = decode_attention(q, k_at, v_at, valid)
    x = x_tok + (o.reshape(B, -1) @ lp["attn"]["wo"])
    if cfg.family == "moe":
        y, _ = moe_lib.moe_apply(lp["moe"], rmsnorm(x[:, None], lp["ln2"], cfg.norm_eps), cfg)
        x = x + y[:, 0]
    else:
        y = mlp_apply(lp["mlp"], rmsnorm(x[:, None], lp["ln2"], cfg.norm_eps), cfg.act)
        x = x + y[:, 0]
    if ks is not None:
        return x, kc, vc, ks, vs
    return x, kc, vc


def forward_prefill(params, tokens, plen, cfg: ModelConfig):
    """One causal pass over padded prompts: the decode cache's rows at once.

    tokens: [B, S] int32, each row a prompt of ``plen`` tokens then padding;
    plen: [B] or a scalar.  Returns (last_logits [B, V] at position
    ``plen - 1``, {"k", "v"} [L, B, S, KV, hd]): each layer's post-RoPE K/V
    rows, which the per-token :func:`forward_decode` loop would have written
    at positions ``< plen``, and zeros from ``plen`` on.  Padding follows
    the prompt, so the causal mask keeps it out of every live row, and only
    row ``plen - 1`` goes through the final norm and the head.

    Dense stacks only: every other family's decode cache holds what one
    pass does not give (``distributed/serve.py:prefill_path``).
    """
    if cfg.family != "dense" or cfg.pattern_local:
        raise ValueError(f"{cfg.name}: one-pass prefill serves dense stacks only")
    B, S = tokens.shape
    plen = jnp.broadcast_to(jnp.asarray(plen, jnp.int32), (B,))
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.compute_dtype))
    x = constrain(x, "tokens")
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        h, k, v = _attn_block_kv(
            lp, h, cfg, positions,
            theta=cfg.rope_theta, window=cfg.sliding_window, impl=cfg.attn_impl,
        )
        h, _ = _mlp_block(lp, h, cfg)
        return h, (k, v)

    x, (k, v) = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    live = (jnp.arange(S)[None, :] < plen[:, None])[None, :, :, None, None]
    kv = {"k": jnp.where(live, k, 0), "v": jnp.where(live, v, 0)}
    x = jnp.take_along_axis(x, (plen - 1)[:, None, None], axis=1)  # [B, 1, d]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, x, cfg)[:, 0], kv


def forward_decode(params, tokens, cache, cache_len, cfg: ModelConfig):
    """One decode step.  tokens: [B] int32.  Returns (logits [B,V], cache)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.compute_dtype))

    if cfg.family in ("dense", "moe", "vlm") and not cfg.pattern_local:
        int8 = "k_scale" in cache

        if int8:
            def body(carry, xs):
                lp, kc, vc, ks, vs = xs
                h, kc, vc, ks, vs = _decode_attn_layer(
                    lp, carry, kc, vc, cache_len, cfg,
                    theta=cfg.rope_theta, window=cfg.sliding_window,
                    ks=ks, vs=vs,
                )
                return h, (kc, vc, ks, vs)

            x, (knew, vnew, ksn, vsn) = jax.lax.scan(
                body, x,
                (params["layers"], cache["k"], cache["v"],
                 cache["k_scale"], cache["v_scale"]),
                unroll=cfg.scan_unroll,
            )
            cache = {"k": knew, "v": vnew, "k_scale": ksn, "v_scale": vsn}
        else:
            def body(carry, xs):
                lp, kc, vc = xs
                h, kc, vc = _decode_attn_layer(
                    lp, carry, kc, vc, cache_len, cfg,
                    theta=cfg.rope_theta, window=cfg.sliding_window,
                )
                return h, (kc, vc)

            x, (knew, vnew) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]), unroll=cfg.scan_unroll)
            cache = {"k": knew, "v": vnew}

    elif cfg.pattern_local:  # gemma3
        theta_g = cfg.rope_theta_global or cfg.rope_theta

        def local_body(carry, xs):
            lp, kc, vc = xs
            h, kc, vc = _decode_attn_layer(
                lp, carry, kc, vc, cache_len, cfg,
                theta=cfg.rope_theta, window=cfg.local_window,
            )
            return h, (kc, vc)

        def group_body(carry, xs):
            gp_local, lkc, lvc, gp_global, gkc, gvc = xs
            h, (lk, lv) = jax.lax.scan(local_body, carry, (gp_local, lkc, lvc), unroll=cfg.scan_unroll)
            h, gk, gv = _decode_attn_layer(
                gp_global, h, gkc, gvc, cache_len, cfg, theta=theta_g, window=None
            )
            return h, (lk, lv, gk, gv)

        x, (lk, lv, gk, gv) = jax.lax.scan(
            group_body,
            x,
            (
                params["local_layers"], cache["local_k"], cache["local_v"],
                params["global_layers"], cache["global_k"], cache["global_v"],
            ),
            unroll=cfg.scan_unroll,
        )
        cache = {"local_k": lk, "local_v": lv, "global_k": gk, "global_v": gv}

    elif cfg.family == "ssm":

        def body(carry, xs):
            lp, h_st, conv_st = xs
            y, new = ssm_lib.mamba1_decode(
                lp["mamba"], rmsnorm(carry[:, None], lp["ln"], cfg.norm_eps)[:, 0],
                {"h": h_st, "conv": conv_st}, cfg,
            )
            return carry + y, (new["h"], new["conv"])

        x, (h_new, conv_new) = jax.lax.scan(body, x, (params["layers"], cache["h"], cache["conv"])
        , unroll=cfg.scan_unroll)
        cache = {"h": h_new, "conv": conv_new}

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(carry, xs):
            lp, h_st, conv_st = xs
            y, new = ssm_lib.mamba2_decode(
                lp["mamba"], rmsnorm(carry[:, None], lp["ln"], cfg.norm_eps)[:, 0],
                {"h": h_st, "conv": conv_st}, cfg,
            )
            return carry + y, (new["h"], new["conv"])

        def group_body(carry, xs):
            gp, mh, mconv, akc, avc = xs
            h, (mh2, mc2) = jax.lax.scan(mamba_body, carry, (gp, mh, mconv), unroll=cfg.scan_unroll)
            h, ak2, av2 = _decode_attn_layer(
                shared, h, akc, avc, cache_len, cfg, theta=cfg.rope_theta, window=None
            )
            return h, (mh2, mc2, ak2, av2)

        x, (mh, mc, ak, av) = jax.lax.scan(
            group_body,
            x,
            (
                params["mamba_groups"], cache["m_h"], cache["m_conv"],
                cache["attn_k"], cache["attn_v"],
            ),
            unroll=cfg.scan_unroll,
        )
        cache = {"m_h": mh, "m_conv": mc, "attn_k": ak, "attn_v": av}
    else:
        raise ValueError(cfg.family)

    x = rmsnorm(x[:, None], params["final_norm"], cfg.norm_eps)[:, 0]
    return _logits(params, x, cfg), cache


def _paged_attn_layer(lp, x_tok, kp, vp, tables, lengths, pb, off, cfg, *,
                      theta):
    """Decode attention layer over block-paged K/V pools.

    x_tok: [S, d]; kp/vp: [N, bs, KV, hd] physical pools (one layer);
    tables: [S, nb]; lengths: [S]; (pb, off): precomputed physical
    (block, offset) of each slot's write (trash-redirected for masked
    slots).  The Pallas paged-attention kernel reads K/V through the block
    table — no contiguous views are materialized.
    """
    from repro.kernels.flash_attention.ops import paged_attention

    S = x_tok.shape[0]
    h = rmsnorm(x_tok[:, None], lp["ln1"], cfg.norm_eps)
    pos = lengths[:, None]
    q, k, v = qkv_project(lp["attn"], h, cfg, pos, theta)
    kp = kp.at[pb, off].set(k[:, 0])
    vp = vp.at[pb, off].set(v[:, 0])
    o = paged_attention(q[:, 0], kp, vp, tables, lengths + 1)
    x = x_tok + (o.reshape(S, -1) @ lp["attn"]["wo"])
    if cfg.family == "moe":
        y, _ = moe_lib.moe_apply(
            lp["moe"], rmsnorm(x[:, None], lp["ln2"], cfg.norm_eps), cfg
        )
        x = x + y[:, 0]
    else:
        y = mlp_apply(
            lp["mlp"], rmsnorm(x[:, None], lp["ln2"], cfg.norm_eps), cfg.act
        )
        x = x + y[:, 0]
    return x, kp, vp


def forward_decode_paged(params, tokens, pages, tables, slot_state, lengths,
                         cfg: ModelConfig, *, block_size: int, write_ok=None):
    """One batched decode step over a block-paged cache (DESIGN.md S14).

    tokens: [S] int32; pages: paged cache pools (``k``/``v`` [L,N,bs,KV,hd]
    for dense/moe/vlm, ``attn_k``/``attn_v`` [G,N,bs,KV,hd] for hybrid);
    tables: [S, nb]; slot_state: per-slot leaves (hybrid ``m_h``/``m_conv``);
    lengths: [S]; write_ok: [S] bool (False redirects the slot's cache write
    to the trash block 0).  Returns (logits [S, V], pages, slot_state).

    Attention runs through the Pallas paged kernel per layer; all other math
    matches :func:`forward_decode`.  int8 KV quantization is served by the
    gather path instead (``make_paged_pool_decode_step(attn="gather")``).
    """
    if "k_scale" in pages:
        raise NotImplementedError(
            "int8 paged decode is served by the gather path (attn='gather')"
        )
    S = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.compute_dtype))
    ok = jnp.ones((S,), bool) if write_ok is None else write_ok
    pb = jnp.take_along_axis(tables, (lengths // block_size)[:, None], axis=1)[:, 0]
    pb = jnp.where(ok, pb, 0)
    off = jnp.where(ok, lengths % block_size, 0)

    if cfg.family in ("dense", "moe", "vlm") and not cfg.pattern_local:

        def body(carry, xs):
            lp, kp, vp = xs
            h, kp, vp = _paged_attn_layer(
                lp, carry, kp, vp, tables, lengths, pb, off, cfg,
                theta=cfg.rope_theta,
            )
            return h, (kp, vp)

        x, (k2, v2) = jax.lax.scan(
            body, x, (params["layers"], pages["k"], pages["v"]),
            unroll=cfg.scan_unroll,
        )
        pages2 = {"k": k2, "v": v2}
        slot2 = {}

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(carry, xs):
            lp, h_st, conv_st = xs
            y, new = ssm_lib.mamba2_decode(
                lp["mamba"], rmsnorm(carry[:, None], lp["ln"], cfg.norm_eps)[:, 0],
                {"h": h_st, "conv": conv_st}, cfg,
            )
            return carry + y, (new["h"], new["conv"])

        def group_body(carry, xs):
            gp, mh, mconv, akp, avp = xs
            h, (mh2, mc2) = jax.lax.scan(
                mamba_body, carry, (gp, mh, mconv), unroll=cfg.scan_unroll
            )
            h, ak2, av2 = _paged_attn_layer(
                shared, h, akp, avp, tables, lengths, pb, off, cfg,
                theta=cfg.rope_theta,
            )
            return h, (mh2, mc2, ak2, av2)

        x, (mh, mc, ak, av) = jax.lax.scan(
            group_body, x,
            (params["mamba_groups"], slot_state["m_h"], slot_state["m_conv"],
             pages["attn_k"], pages["attn_v"]),
            unroll=cfg.scan_unroll,
        )
        pages2 = {"attn_k": ak, "attn_v": av}
        slot2 = {"m_h": mh, "m_conv": mc}
    else:
        raise ValueError(f"family {cfg.family!r} has no paged decode path")

    x = rmsnorm(x[:, None], params["final_norm"], cfg.norm_eps)[:, 0]
    return _logits(params, x, cfg), pages2, slot2
