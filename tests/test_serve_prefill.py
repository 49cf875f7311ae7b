"""Single-dispatch cached prefill == per-token decode loop (serve path).

``make_cached_prefill_step`` scans the decode step over the prompt inside
one jitted program; the launcher used to dispatch a Python loop of decode
steps per prompt token.  Both must produce the same cache and the same
generations.  ``transformer.forward_prefill``, the one causal pass that
admits a dense prompt into a serving pool, is held to the same loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import registry
from repro.distributed import step as step_lib
from repro.models import transformer


def _mesh():
    return jax.make_mesh(
        (1,), ("data",), devices=jax.devices()[:1],
        axis_types=(AxisType.Auto,),
    )


@pytest.mark.parametrize("arch", ["llama3.2-1b", "zamba2-2.7b"])
def test_cached_prefill_matches_per_token_loop(arch):
    cfg = registry.get_smoke_config(arch)
    if cfg.is_encoder_only:
        pytest.skip("encoder-only: no decode serving")
    mesh = _mesh()
    B, S, G = 2, 8, 4
    max_len = S + G
    serve_step, _ = step_lib.make_serve_step(cfg, mesh)
    prefill_step, _ = step_lib.make_cached_prefill_step(cfg, mesh)

    with mesh:
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (B, S), 0, cfg.vocab
        )
        jstep = jax.jit(serve_step)
        jprefill = jax.jit(prefill_step)

        # reference: the historical per-token Python loop
        cache_ref = transformer.init_cache(cfg, B, max_len)
        for i in range(S):
            logits_ref, cache_ref = jstep(
                params, prompt[:, i], cache_ref, jnp.int32(i)
            )

        # one jitted prefill dispatch
        logits_new, cache_new = jprefill(
            params, prompt, transformer.init_cache(cfg, B, max_len)
        )

    np.testing.assert_allclose(
        np.asarray(logits_new, np.float32), np.asarray(logits_ref, np.float32),
        rtol=1e-5, atol=1e-5,
    )

    # prefill+decode output is unchanged: greedy generations from both
    # caches must be token-identical
    def decode(logits, cache):
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out = []
        for i in range(G):
            out.append(np.asarray(toks))
            logits, cache = jstep(params, toks, cache, jnp.int32(S + i))
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        return np.stack(out, 1)

    with mesh:
        ids_ref = decode(logits_ref, cache_ref)
        ids_new = decode(logits_new, cache_new)
    np.testing.assert_array_equal(ids_new, ids_ref)


# ---------------------------------------------------------------------------
# One-pass prefill (transformer.forward_prefill) == per-token decode loop
# ---------------------------------------------------------------------------

_LMAX, _GEN = 8, 4


def _decode_loop(jstep, cfg, params, prompts, plens, max_len):
    """Each prompt alone through the per-token ``forward_decode`` loop:
    (last logits [B, V], K/V cache [L, B, max_len, KV, hd])."""
    logits, caches = [], []
    for b in range(prompts.shape[0]):
        cache = transformer.init_cache(cfg, 1, max_len)
        for i in range(int(plens[b])):
            lg, cache = jstep(params, prompts[b:b + 1, i], cache, jnp.int32(i))
        logits.append(lg[0])
        caches.append(cache)
    cache = {n: jnp.concatenate([c[n] for c in caches], axis=1) for n in ("k", "v")}
    return jnp.stack(logits), cache


def _greedy(jstep, params, logits, cache, plens):
    """``_GEN`` greedy tokens per row, each row decoded at its own length."""
    out = []
    for b in range(logits.shape[0]):
        c = {n: cache[n][:, b:b + 1] for n in cache}
        lg, toks = logits[b:b + 1], []
        for i in range(_GEN):
            toks.append(int(jnp.argmax(lg, -1)[0]))
            lg, c = jstep(params, jnp.asarray(toks[-1:], jnp.int32), c,
                          jnp.int32(int(plens[b]) + i))
        out.append(toks)
    return np.asarray(out)


@pytest.mark.parametrize("plen", [1, 5, _LMAX])
@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm-2b"])
def test_forward_prefill_matches_per_token_decode(arch, plen):
    """llama3.2-1b: GQA (two query heads a K/V head); minicpm-2b: MHA.
    Row 0 holds ``plen`` live tokens, row 1 the complement, so a batch of
    per-row lengths pads both rows differently."""
    cfg = registry.get_smoke_config(arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, _LMAX), 0, cfg.vocab)
    plens = np.asarray([plen, _LMAX + 1 - plen], np.int32)
    max_len = _LMAX + _GEN
    jstep = jax.jit(lambda p, t, c, i: transformer.forward_decode(p, t, c, i, cfg))

    logits, kv = jax.jit(
        lambda p, t, n: transformer.forward_prefill(p, t, n, cfg)
    )(params, prompts, jnp.asarray(plens))
    ref_logits, ref_cache = _decode_loop(jstep, cfg, params, prompts, plens, max_len)

    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    for n in ("k", "v"):
        assert kv[n].shape == (cfg.n_layers, 2, _LMAX, cfg.n_kv_heads, cfg.head_dim)
        for b, pl in enumerate(plens):
            np.testing.assert_allclose(
                np.asarray(kv[n][:, b, :pl]), np.asarray(ref_cache[n][:, b, :pl]),
                rtol=1e-5, atol=1e-5,
            )
            assert not np.asarray(kv[n][:, b, pl:]).any()

    cache = {n: jnp.zeros_like(ref_cache[n]).at[:, :, :_LMAX].set(kv[n])
             for n in ("k", "v")}
    np.testing.assert_array_equal(
        _greedy(jstep, params, logits, cache, plens),
        _greedy(jstep, params, ref_logits, ref_cache, plens),
    )


def test_forward_prefill_caches_kv_heads_before_the_repeat(monkeypatch):
    """Under tensor parallelism GQA K/V heads are repeated before attention;
    the rows handed to the cache keep ``n_kv_heads`` and their values."""
    from repro.distributed import sharding

    cfg = registry.get_smoke_config("llama3.2-1b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, _LMAX), 0, cfg.vocab)
    base = transformer.forward_prefill(params, prompts, 5, cfg)
    monkeypatch.setattr(sharding, "kv_repeat_factor", lambda H, KV: H // KV)
    rep = transformer.forward_prefill(params, prompts, 5, cfg)
    np.testing.assert_allclose(np.asarray(rep[0]), np.asarray(base[0]),
                               rtol=1e-5, atol=1e-5)
    for n in ("k", "v"):
        assert rep[1][n].shape[3] == cfg.n_kv_heads
        np.testing.assert_array_equal(np.asarray(rep[1][n]), np.asarray(base[1][n]))
