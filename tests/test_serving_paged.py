"""Block-paged serving (repro.serving.paged, DESIGN.md S14).

Core claims under test:

1. **Bit-equivalence** — paged decode (block tables, slot recycling, mixed
   admission, prefix sharing) produces token-for-token identical outputs
   to the contiguous pool AND to decoding each request alone in a static
   batch, at termination agreement dp ∈ {1, 2, 3}, on a dense and a hybrid
   (SSM+attention) arch.  The mechanism: the paged step gathers each
   slot's blocks into exactly the contiguous layout and runs the unchanged
   decode vmap, so the jaxpr — and therefore every bit — matches.
2. **Prefix sharing** — identical system prefixes map to the *same*
   physical blocks (stored once, refcounted); sharers retire
   independently; shared blocks are never written by a sharer's decode.
3. **Block accounting** — recycling returns every block to the allocator;
   admission is backpressured (a request waits in the queue when the pool
   is out of blocks) instead of deadlocking or evicting.
4. **Capacity honesty** — a slot frozen at its reserved capacity is
   force-retired and surfaced in ``summary()['forced_at_capacity']``
   rather than silently spinning against its budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import registry
from repro.distributed import step as step_lib
from repro.models import transformer
from repro.serving import (
    PagedDecodePool,
    Request,
    ServeConfig,
    ServeEngine,
    make_workload,
)


def _mesh(n=1):
    return jax.make_mesh(
        (n,), ("data",), devices=jax.devices()[:n],
        axis_types=(AxisType.Auto,),
    )


def _solo_decode(cfg, mesh, params, prompt, max_new):
    """The request decoded alone in a static batch (the PR-4 serve path)."""
    serve_step, _ = step_lib.make_serve_step(cfg, mesh)
    prefill_step, _ = step_lib.make_cached_prefill_step(cfg, mesh)
    jstep, jprefill = jax.jit(serve_step), jax.jit(prefill_step)
    S = int(prompt.shape[0])
    with mesh:
        cache = transformer.init_cache(cfg, 1, S + max_new + 1)
        logits, cache = jprefill(params, jnp.asarray(prompt[None]), cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        for k in range(max_new - 1):
            logits, cache = jstep(
                params, jnp.asarray(toks[-1:], jnp.int32), cache,
                jnp.int32(S + k),
            )
            toks.append(int(jnp.argmax(logits, -1)[0]))
    return np.asarray(toks, np.int32)


def _requests(cfg, *, seed=3, share_prefix=0):
    """5 requests over 2 slots: recycling forced, admissions mid-decode,
    mixed lengths.  ``share_prefix`` tokens are common to all prompts."""
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab, size=share_prefix)
    lens = (1, 2, 4, 3, 2) if share_prefix else (3, 5, 8, 5, 3)
    prompts = [
        np.concatenate([pre, rng.integers(0, cfg.vocab, size=L)]).astype(
            np.int64
        )
        for L in lens
    ]
    max_new = [6, 4, 7, 5, 6]
    return [
        Request(id=i, arrival=[0, 0, 2, 5, 7][i], prompt=prompts[i],
                max_new=max_new[i])
        for i in range(5)
    ]


def _run(workload_name, cfg, mesh, reqs, *, dp=1, **kw):
    wl = make_workload(
        workload_name, cfg=cfg, mesh=mesh, slots=2, max_len=24,
        max_prompt_len=12, seed=0, **kw,
    )
    eng = ServeEngine(wl, ServeConfig(dp=dp))
    res = eng.run(list(reqs))
    return wl, eng, res


# ---------------------------------------------------------------------------
# 1. Paged == contiguous == solo, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp", [1, 2, 3])
def test_paged_matches_contiguous_and_solo_dense(dp):
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    reqs = _requests(cfg)
    wl_c, _, res_c = _run("llm_decode", cfg, mesh, reqs, dp=dp)
    wl_p, _, res_p = _run("llm_decode_paged", cfg, mesh, reqs, dp=dp,
                          block_size=8)
    for r in reqs:
        np.testing.assert_array_equal(res_c[r.id].output, res_p[r.id].output)
        solo = _solo_decode(
            cfg, mesh, wl_c.params, np.asarray(r.prompt, np.int64),
            wl_c.clamp_max_new(r),
        )
        np.testing.assert_array_equal(res_p[r.id].output, solo)
    # paging is strictly denser per byte at equal capacity is a bench
    # claim; here just assert the accounting drained cleanly
    assert wl_p.pool.allocator.used_blocks == 0
    wl_p.pool.allocator.check()


@pytest.mark.slow
@pytest.mark.parametrize("dp", [1, 3])
def test_paged_matches_contiguous_hybrid(dp):
    """Hybrid (Mamba + attention): attn leaves paged, SSM state per-slot."""
    cfg = registry.get_smoke_config("zamba2-2.7b")
    mesh = _mesh()
    reqs = _requests(cfg)
    _, _, res_c = _run("llm_decode", cfg, mesh, reqs, dp=dp)
    _, _, res_p = _run("llm_decode_paged", cfg, mesh, reqs, dp=dp,
                       block_size=8)
    for r in reqs:
        np.testing.assert_array_equal(res_c[r.id].output, res_p[r.id].output)


@pytest.mark.slow
def test_paged_matches_contiguous_multidevice():
    """Same parity with the cache actually sharded over a 2-device mesh."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh(2)
    reqs = _requests(cfg, seed=11, share_prefix=8)
    _, _, res_c = _run("llm_decode", cfg, mesh, reqs, dp=2)
    wl_p, _, res_p = _run("llm_decode_paged", cfg, mesh, reqs, dp=2,
                          block_size=8)
    for r in reqs:
        np.testing.assert_array_equal(res_c[r.id].output, res_p[r.id].output)
    assert wl_p.prefix_saved_blocks > 0


def test_pallas_attn_matches_gather():
    """The paged Pallas kernel path retires the same tokens as the
    bit-exact gather path (kernel numerics differ only below argmax)."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    reqs = _requests(cfg, seed=5)
    _, _, res_g = _run("llm_decode_paged", cfg, mesh, reqs, block_size=8,
                       attn="gather")
    _, _, res_k = _run("llm_decode_paged", cfg, mesh, reqs, block_size=8,
                       attn="pallas")
    for r in reqs:
        np.testing.assert_array_equal(res_g[r.id].output, res_k[r.id].output)


# ---------------------------------------------------------------------------
# 2. Prefix sharing
# ---------------------------------------------------------------------------


def test_prefix_sharing_stores_blocks_once():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    pool = PagedDecodePool(cfg, mesh, slots=4, max_len=24, max_prompt_len=12,
                           block_size=8)
    with mesh:
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    sys_prefix = rng.integers(0, cfg.vocab, size=8)  # exactly one block
    prompts = [
        np.concatenate([sys_prefix, rng.integers(0, cfg.vocab, size=3)])
        for _ in range(4)
    ]
    for s, p in enumerate(prompts):
        pool.admit(params, p, s, max_new=6)
    # all four slots map logical block 0 to the same physical block
    shared = {pool.slot_blocks[s][0] for s in range(4)}
    assert len(shared) == 1
    bid = shared.pop()
    assert pool.allocator.ref[bid] == 4
    assert pool.prefix_saved_blocks == 3  # stored once, adopted thrice
    # later blocks are private
    assert len({pool.slot_blocks[s][1] for s in range(4)}) == 4

    # decode never writes a shared block
    snap = {
        n: np.asarray(pool.state["pages"][n][:, bid])
        for n in pool.state["pages"]
    }
    active = jnp.ones((4,), bool)
    state = pool.state
    for _ in range(5):
        state = pool.device_step(params, state, active)
    for n, before in snap.items():
        np.testing.assert_array_equal(before, np.asarray(state["pages"][n][:, bid]))

    # sharers retire independently; the block frees with the last one
    for s in range(3):
        pool.release_slot(s)
        assert pool.allocator.ref[bid] == 3 - s
    assert pool.allocator.peek(sys_prefix.astype(np.int32).tobytes()) == bid
    pool.release_slot(3)
    assert pool.allocator.ref[bid] == 0
    assert pool.allocator.peek(sys_prefix.astype(np.int32).tobytes()) is None
    assert pool.allocator.used_blocks == 0
    pool.allocator.check()


def test_prefix_sharing_served_tokens_identical():
    """Shared-prefix requests through the engine: same tokens as with
    sharing disabled, and fewer blocks touched."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    reqs = _requests(cfg, seed=9, share_prefix=8)
    wl_s, _, res_s = _run("llm_decode_paged", cfg, mesh, reqs, block_size=8,
                          share_prefixes=True)
    wl_n, _, res_n = _run("llm_decode_paged", cfg, mesh, reqs, block_size=8,
                          share_prefixes=False)
    for r in reqs:
        np.testing.assert_array_equal(res_s[r.id].output, res_n[r.id].output)
    assert wl_s.prefix_saved_blocks > 0
    assert wl_n.prefix_saved_blocks == 0


# ---------------------------------------------------------------------------
# 3. Block accounting: recycling + backpressure
# ---------------------------------------------------------------------------


def test_backpressure_waits_for_blocks():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    rng = np.random.default_rng(13)
    # 2 slots but only enough blocks for one request at a time
    wl = make_workload(
        "llm_decode_paged", cfg=cfg, mesh=mesh, slots=2, max_len=16,
        max_prompt_len=8, seed=0, block_size=8, num_blocks=3,
    )
    eng = ServeEngine(wl, ServeConfig())
    reqs = [
        Request(id=i, prompt=rng.integers(0, cfg.vocab, size=4), max_new=6)
        for i in range(2)
    ]
    res = eng.run(reqs)
    assert len(res) == 2  # both completed despite the block famine
    # the second could only be admitted after the first retired its blocks
    first, second = sorted(res.values(), key=lambda r: r.admit_tick)
    assert second.admit_tick >= first.retire_tick
    assert wl.pool.allocator.used_blocks == 0
    wl.pool.allocator.check()


def test_never_fitting_request_raises():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    wl = make_workload(
        "llm_decode_paged", cfg=cfg, mesh=mesh, slots=1, max_len=16,
        max_prompt_len=8, seed=0, block_size=8, num_blocks=2,
    )
    with pytest.raises(ValueError, match="never be admitted"):
        wl.can_admit(Request(id=0, prompt=np.arange(8), max_new=20))


# ---------------------------------------------------------------------------
# 4. Capacity honesty: forced_at_capacity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", ["llm_decode", "llm_decode_paged"])
def test_forced_at_capacity_surfaced(workload):
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    wl = make_workload(
        workload, cfg=cfg, mesh=mesh, slots=1, max_len=16,
        max_prompt_len=8, seed=0,
        **({"block_size": 8} if workload == "llm_decode_paged" else {}),
    )
    # defeat the budget clamp so the request's budget exceeds the cache:
    # the slot freezes at capacity with the budget still unspent
    wl.clamp_max_new = lambda req: int(req.max_new)
    eng = ServeEngine(wl, ServeConfig())
    res = eng.run([Request(id=0, prompt=np.arange(4) + 1, max_new=500)])
    s = eng.summary()
    assert s["forced_at_capacity"] == 1
    assert not res[0].converged
    # it produced exactly the tokens the cache had room for, then stopped
    assert res[0].n_tokens < 500
    assert eng.tick < 100  # retired promptly, not after 500 ticks


def test_budget_retirement_not_counted_as_capacity(
):
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    reqs = _requests(cfg)
    _, eng, res = _run("llm_decode_paged", cfg, mesh, reqs, dp=3,
                       block_size=8)
    assert eng.summary()["forced_at_capacity"] == 0
    assert all(r.converged for r in res.values())
