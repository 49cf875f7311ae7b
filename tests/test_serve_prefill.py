"""Single-dispatch cached prefill == per-token decode loop (serve path).

``make_cached_prefill_step`` scans the decode step over the prompt inside
one jitted program; the launcher used to dispatch a Python loop of decode
steps per prompt token.  Both must produce the same cache and the same
generations.
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
