"""Elastic serving: resize the engine's replica extent under live traffic
(DESIGN.md S15).

In-process units for the machinery the serving chaos suite
(``test_chaos_serving.py``) drives end to end:

- the stacked MRD sum-broadcast (``mrd_broadcast_stacked``) is bit-exact
  at power-of-two and non-power-of-two extents, for float/int/bool and
  zero-size leaves — the grow path's state transfer;
- the termination protocols survive ``migrate`` mid-agreement-window: a
  locally-converged surviving replica never retires a slot early after a
  5→3 shrink or a 3→5 grow, the staged reduction restarts at the new
  extent, and certified bounds still hold at retirement;
- :meth:`ServeEngine.resize` under live fixed-point and LLM traffic
  loses no request, re-prefills no slot, and (LLM) retires tokens
  bit-identical to an uninterrupted run;
- bounded capacity requeue (``ServeConfig.max_retries``) surfaces retry
  counts, and a crashed fused dispatch rolls its block reservations back
  to the allocator instead of leaking them;
- the :class:`ElasticServeController` keep-map algebra (ReplicaSet,
  clamp_min_extent) and the min-extent spare/resurrect path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import registry
from repro.distributed.serve import mrd_broadcast_stacked
from repro.runtime import (
    ElasticServeController,
    ReplicaSet,
    ResizeDecision,
    clamp_min_extent,
)
from repro.serving import (
    Request,
    ServeConfig,
    ServeEngine,
    TerminationConfig,
    get_termination,
    make_workload,
)
from repro.serving.termination import make_signals


def _mesh():
    return jax.make_mesh(
        (1,), ("data",), devices=jax.devices()[:1],
        axis_types=(AxisType.Auto,),
    )


def _sig(dp, slots, *, tick, active, admit_tick, residual, eps=1e-3):
    return make_signals(
        tokens=jnp.zeros((slots,), jnp.int32),
        new_tokens=jnp.full((slots,), 5, jnp.int32),
        eos=jnp.full((slots,), -1, jnp.int32),
        max_new=jnp.full((slots,), 1000, jnp.int32),
        eps=jnp.full((slots,), eps, jnp.float32),
        active=jnp.asarray(active),
        admit_tick=jnp.asarray(admit_tick, jnp.int32),
        tick=jnp.int32(tick),
        residual=jnp.asarray(residual, jnp.float32),
    )


# ---------------------------------------------------------------------------
# 1. Stacked MRD broadcast: bit-exact at any extent
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [2, 3, 5])
def test_mrd_broadcast_stacked_bit_exact(p):
    rng = np.random.default_rng(17)
    tree = {
        "f32": rng.standard_normal((7, 5)).astype(np.float32) * 1e3,
        "i32": rng.integers(-(2**30), 2**30, size=(11,)).astype(np.int32),
        "flags": rng.random((6,)) < 0.5,
        "empty": np.zeros((0, 4), np.float32),
    }
    out = mrd_broadcast_stacked(tree, p, src=0)
    for k in tree:
        got, want = np.asarray(out[k]), tree[k]
        assert got.dtype == want.dtype and got.shape == want.shape, k
        if want.dtype == np.float32:
            np.testing.assert_array_equal(
                got.view(np.uint32), want.view(np.uint32),
                err_msg=f"p={p} leaf {k} not bit-identical",
            )
        else:
            np.testing.assert_array_equal(got, want, err_msg=f"p={p} {k}")


# ---------------------------------------------------------------------------
# 2. Termination migrate mid-window (satellite: 5→3 and 3→5)
# ---------------------------------------------------------------------------


def test_residual_interval_migrate_shrink_mid_window():
    """5→3 mid-window: the surviving locally-converged replica (rank 0)
    must not retire the slot after the shrink — the agreed value is still
    the max over the *new* replica group, and the migrated per-replica
    interval windows keep the survivors' high-water marks."""
    term = get_termination("residual_interval")
    t5 = TerminationConfig(dp=5, eps=1e-3, window=0)
    t3 = TerminationConfig(dp=3, eps=1e-3, window=0)
    slots = 2
    st = term.init(t5, slots)
    active = np.ones((slots,), bool)
    admit = np.zeros((slots,), np.int32)

    # replica 0 locally converged, replica 1 far from it
    mixed5 = np.full((5, slots), 1e-6, np.float32)
    mixed5[1, :] = 1.0
    tick = 0
    for _ in range(term.cycle_length(t5) // 2 + 1):  # stop mid-cycle
        st, retire = term.tick(
            st, _sig(5, slots, tick=tick, active=active, admit_tick=admit,
                     residual=mixed5), t5)
        assert not bool(np.asarray(retire).any())
        tick += 1

    # kill replicas 3 and 4; survivors 0,1,2 keep their rows (the derived
    # window length differs across extents, so this also exercises the
    # conservative max-fill reshape)
    st = term.migrate(st, (0, 1, 2), t3, slots)

    mixed3 = np.full((3, slots), 1e-6, np.float32)
    mixed3[1, :] = 1.0
    cyc3 = term.cycle_length(t3)
    for _ in range(3 * cyc3 + 3):
        st, retire = term.tick(
            st, _sig(3, slots, tick=tick, active=active, admit_tick=admit,
                     residual=mixed3), t3)
        assert not bool(np.asarray(retire).any()), (
            "retired while a surviving replica still reports 1.0"
        )
        tick += 1

    # everyone converges -> certification within window + two cycles
    low = np.full((3, slots), 1e-6, np.float32)
    window = t3.window or cyc3 + 1
    retired = np.zeros((slots,), bool)
    for _ in range(window + 3 * cyc3):
        st, retire = term.tick(
            st, _sig(3, slots, tick=tick, active=active, admit_tick=admit,
                     residual=low), t3)
        retired |= np.asarray(retire)
        active = active & ~np.asarray(retire)
        tick += 1
        if retired.all():
            break
    assert retired.all(), "did not certify after the shrink"
    cert = np.asarray(st["certified"])
    assert (cert < 1e-3).all(), cert


def test_residual_interval_migrate_grow_mid_window():
    """3→5 mid-window: joiners get fresh (conservative) rows, the staged
    reduction restarts at the new extent — so nothing can retire before a
    full agreement cycle at dp=5 completes, a joiner's high residual blocks
    retirement, and the certified bound still holds once everyone is low."""
    term = get_termination("residual_interval")
    t3 = TerminationConfig(dp=3, eps=1e-3, window=0)
    t5 = TerminationConfig(dp=5, eps=1e-3, window=0)
    slots = 2
    st = term.init(t3, slots)
    active = np.ones((slots,), bool)
    admit = np.zeros((slots,), np.int32)

    mixed3 = np.full((3, slots), 1e-6, np.float32)
    mixed3[2, :] = 1.0
    tick = 0
    for _ in range(term.cycle_length(t3) // 2 + 1):
        st, retire = term.tick(
            st, _sig(3, slots, tick=tick, active=active, admit_tick=admit,
                     residual=mixed3), t3)
        assert not bool(np.asarray(retire).any())
        tick += 1

    st = term.migrate(st, (0, 1, 2, None, None), t5, slots)
    cyc5 = term.cycle_length(t5)

    # all survivors low but the new joiner (rank 4) still high: the cycle
    # restart means no retirement within the first new cycle, and none
    # after either while the joiner's residual dominates the agreed max
    joiner_high = np.full((5, slots), 1e-6, np.float32)
    joiner_high[4, :] = 1.0
    for k in range(3 * cyc5 + 3):
        st, retire = term.tick(
            st, _sig(5, slots, tick=tick, active=active, admit_tick=admit,
                     residual=joiner_high), t5)
        assert not bool(np.asarray(retire).any()), f"retired at tick {k}"
        tick += 1

    low = np.full((5, slots), 1e-6, np.float32)
    window = t5.window or cyc5 + 1
    retired = np.zeros((slots,), bool)
    for _ in range(window + 3 * cyc5):
        st, retire = term.tick(
            st, _sig(5, slots, tick=tick, active=active, admit_tick=admit,
                     residual=low), t5)
        retired |= np.asarray(retire)
        active = active & ~np.asarray(retire)
        tick += 1
        if retired.all():
            break
    assert retired.all(), "did not certify after the grow"
    assert (np.asarray(st["certified"]) < 1e-3).all()


@pytest.mark.parametrize("protocol", ["eos_maxlen", "residual_inexact"])
def test_migrate_preserves_certified_latch(protocol):
    """Every protocol's migrate keeps the per-slot certified latch — a
    request that certified before the resize stays certified after it."""
    term = get_termination(protocol)
    t4 = TerminationConfig(dp=4, eps=1e-3)
    t3 = TerminationConfig(dp=3, eps=1e-3)
    st = term.init(t4, 3)
    st["certified"] = jnp.asarray([0.5, 1e-9, 0.5], jnp.float32)
    new = term.migrate(st, (0, 1, 3), t3, 3)
    np.testing.assert_array_equal(
        np.asarray(new["certified"]), np.asarray(st["certified"])
    )


# ---------------------------------------------------------------------------
# 3. Engine resize under live fixed-point traffic (4-visits: 5→3→5)
# ---------------------------------------------------------------------------


def test_fixedpoint_engine_resize_under_traffic():
    eps = 1e-6
    n = 60  # divisible by every visited extent
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=n, dp=5, slots=3,
        damping=0.7, seed=1,
    )
    eng = ServeEngine(wl, ServeConfig(
        scheduler="fcfs", termination="residual_interval", dp=5, eps=eps,
        steps_per_dispatch=4,
    ))
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(6):
        v = rng.random(n).astype(np.float32)
        reqs.append(Request(id=i, arrival=3 * i, payload=v / v.sum(),
                            max_new=800))
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    ev = eng.resize(3, (0, 2, 4), reason="killed 1,3")
    assert ev.kind == "shrink" and (ev.old_dp, ev.new_dp) == (5, 3)
    eng.step()
    eng.step()
    ev = eng.resize(5, (0, 1, 2, None, None), reason="two joiners")
    assert ev.kind == "grow" and (ev.old_dp, ev.new_dp) == (3, 5)
    res = eng.run([])
    assert len(res) == 6
    assert eng.summary()["resizes"] == 2
    for i, r in sorted(res.items()):
        assert r.converged, f"request {i} lost certification across resizes"
        assert r.certified < eps
        v = jnp.asarray(reqs[i].payload)
        x = jnp.asarray(r.output)
        true_res = float(jnp.max(jnp.abs(wl.pool.param_map(x, v) - x)))
        assert true_res < eps, (i, true_res)


def test_resize_rejects_bad_keep_and_noop():
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=12, dp=2, slots=2,
        damping=0.5,
    )
    eng = ServeEngine(wl, ServeConfig(termination="residual_inexact", dp=2))
    with pytest.raises(ValueError, match="keep"):
        eng.resize(3, (0, 1))  # keep map does not cover new_dp
    with pytest.raises(ValueError, match="outside"):
        eng.resize(2, (0, 5))
    assert eng.resize(2, (0, 1)) is None  # identity resize is a no-op
    assert eng.resizes == []


# ---------------------------------------------------------------------------
# 4. LLM: tokens survive grow+shrink bit-identically, zero re-prefill
# ---------------------------------------------------------------------------


def test_llm_tokens_survive_resize_no_reprefill():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=L) for L in (3, 5, 8, 4)]
    max_new = [6, 4, 7, 5]

    def reqs():
        return [
            Request(id=i, arrival=[0, 1, 4, 6][i], prompt=p, max_new=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))
        ]

    wl = make_workload(
        "llm_decode", cfg=cfg, mesh=mesh, slots=2, max_len=24,
        max_prompt_len=8, seed=0,
    )
    # oracle: the same traffic, uninterrupted at dp=2
    want = ServeEngine(wl, ServeConfig(dp=2)).run(reqs())
    assert wl.prefills == len(prompts)

    wl.reset()
    assert wl.prefills == 0
    eng = ServeEngine(wl, ServeConfig(dp=2, steps_per_dispatch=2))
    for r in reqs():
        eng.submit(r)
    eng.step()
    assert eng.resize(3, (0, 1, None), reason="joiner").kind == "grow"
    eng.step()
    assert eng.resize(2, (0, 2), reason="killed 1").kind == "shrink"
    res = eng.run([])

    assert len(res) == len(prompts), "request lost across resize"
    # LLM pool state is slot-indexed and replica-independent: a resize
    # must never re-prefill a slot
    assert wl.prefills == len(prompts), "resize re-prefilled a slot"
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            res[i].output, want[i].output,
            err_msg=f"request {i}: tokens diverged across resize",
        )


# ---------------------------------------------------------------------------
# 5. Bounded capacity requeue (satellite: max_retries)
# ---------------------------------------------------------------------------


def test_capacity_retry_bounded_and_surfaced():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    wl = make_workload(
        "llm_decode_paged", cfg=cfg, mesh=mesh, slots=1, max_len=16,
        max_prompt_len=8, seed=0, block_size=8,
    )
    # defeat the budget clamp so the slot freezes at cache capacity with
    # budget unspent (the forced_at_capacity path)
    wl.clamp_max_new = lambda req: int(req.max_new)
    eng = ServeEngine(wl, ServeConfig(max_retries=2))
    res = eng.run([Request(id=0, prompt=np.arange(4) + 1, max_new=500)])
    s = eng.summary()
    # each attempt hits capacity; after max_retries requeues it retires
    assert s["forced_at_capacity"] == 3
    assert s["retried"] == 2
    assert res[0].retries == 2
    assert not res[0].converged
    assert wl.prefills == 3  # each retry is a fresh admission by design
    # every attempt's block reservation was returned
    assert wl.pool.allocator.used_blocks == 0
    wl.pool.allocator.check()


def test_max_retries_zero_keeps_fail_fast():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    wl = make_workload(
        "llm_decode_paged", cfg=cfg, mesh=mesh, slots=1, max_len=16,
        max_prompt_len=8, seed=0, block_size=8,
    )
    wl.clamp_max_new = lambda req: int(req.max_new)
    eng = ServeEngine(wl, ServeConfig())  # default: no retries
    res = eng.run([Request(id=0, prompt=np.arange(4) + 1, max_new=500)])
    assert eng.summary()["forced_at_capacity"] == 1
    assert eng.summary()["retried"] == 0
    assert res[0].retries == 0 and not res[0].converged


# ---------------------------------------------------------------------------
# 6. Exception-safe block release (satellite: crashed dispatch rollback)
# ---------------------------------------------------------------------------


def test_crashed_dispatch_rolls_back_blocks_and_requeues():
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    wl = make_workload(
        "llm_decode_paged", cfg=cfg, mesh=mesh, slots=2, max_len=16,
        max_prompt_len=8, seed=0, block_size=8,
    )
    rng = np.random.default_rng(13)
    reqs = [
        Request(id=i, prompt=rng.integers(0, cfg.vocab, size=4), max_new=5)
        for i in range(2)
    ]
    free_before = wl.pool.allocator.free_blocks
    eng = ServeEngine(wl, ServeConfig())
    real = eng._jfused

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    eng._jfused = boom
    for r in reqs:
        eng.submit(r)
    with pytest.raises(RuntimeError, match="injected device fault"):
        eng.step()
    # the admitted slots' reservations were rolled back, nothing leaked
    assert wl.pool.allocator.used_blocks == 0
    assert wl.pool.allocator.free_blocks == free_before
    wl.pool.allocator.check()
    # both requests are back in the queue, no slot thinks it is active
    assert sorted(r.id for r in eng.queue) == [0, 1]
    assert all(s is None for s in eng.slot_req)
    assert not eng.active.any()

    # recovery: restore the dispatch and drain — clean re-admissions
    eng._jfused = real
    res = eng.run([])
    assert len(res) == 2 and all(r.converged for r in res.values())
    assert wl.pool.allocator.used_blocks == 0
    wl.pool.allocator.check()


# ---------------------------------------------------------------------------
# 7. Controller algebra: ReplicaSet, clamp_min_extent, spare/resurrect
# ---------------------------------------------------------------------------


def test_replica_set_keep_maps():
    rs = ReplicaSet([0, 1, 2, 3])
    ids, keep = rs.remove({2})
    assert ids == (0, 1, 3) and keep == (0, 1, 3)
    ids, keep = rs.add([4, 5])
    assert ids == (0, 1, 3, 4, 5)
    assert keep == (0, 1, 2, None, None)
    ids, keep = rs.add([4])  # already present: no-op join
    assert ids == (0, 1, 3, 4, 5) and keep == (0, 1, 2, 3, 4)
    with pytest.raises(RuntimeError, match="no live replicas"):
        rs.remove({0, 1, 3, 4, 5})
    with pytest.raises(ValueError, match="duplicate"):
        ReplicaSet([1, 1])


def test_clamp_min_extent():
    d = ResizeDecision("shrink", remove=frozenset({0, 1, 2}), reason="hb")
    # enough survivors: untouched
    assert clamp_min_extent(d, [0, 1, 2, 3], 1) is d
    # all victims spared -> suppressed no-op decision
    out = clamp_min_extent(d, [0, 1, 2], 3)
    assert out.action == "none" and "suppressed" in out.reason
    # partial sparing keeps the lowest ids
    out = clamp_min_extent(d, [0, 1, 2, 3], 3)
    assert out.action == "shrink" and out.remove == frozenset({2})
    assert "clamped" in out.reason
    # non-shrink decisions pass through
    g = ResizeDecision("grow", admit=(7,))
    assert clamp_min_extent(g, [0], 1) is g


def test_controller_min_extent_spares_and_serves_on():
    """Killing every replica must not kill the pool: clamp_min_extent pins
    it at one replica, the spared replica is pressed back into service,
    and all traffic still completes."""
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=60, dp=2, slots=2,
        damping=0.7, seed=1,
    )
    eng = ServeEngine(wl, ServeConfig(
        termination="residual_inexact", dp=2, eps=1e-5,
        steps_per_dispatch=4,
    ))
    ctl = ElasticServeController(eng, policy="shrink_on_failure",
                                 min_extent=1)
    ctl.kill(0)
    ctl.kill(1)
    res = ctl.run([
        Request(id=0, max_new=500),
        Request(id=1, arrival=2, max_new=500),
    ])
    assert len(res) == 2 and all(r.converged for r in res.values())
    assert eng.dp == 1
    assert [(e.old_dp, e.new_dp) for e in ctl.resizes] == [(2, 1)]
    # the spared replica was resurrected, not left flapping
    assert ctl.health[0] == "ok"


def test_controller_rejects_mismatched_replica_ids():
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=12, dp=2, slots=2,
        damping=0.5,
    )
    eng = ServeEngine(wl, ServeConfig(termination="residual_inexact", dp=2))
    with pytest.raises(ValueError, match="replica ids"):
        ElasticServeController(eng, replica_ids=[0, 1, 2])
