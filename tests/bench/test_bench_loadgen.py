"""The load generator: every seed gets the same set of sizes and gaps (the
seed orders an open loop's gaps and draws ids and payloads), and waits and
lag count from due times."""

import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import loadgen, serve_loop  # noqa: E402
from bench.kinds import fixedpoint, llm_paged  # noqa: E402

TRAFFIC = {
    "clients": 6, "ramp_s": 1.5, "requests": 64, "group": 16,
    "prompt_tokens": {"mean": 19.31, "sigma": 0.6, "min": 1, "max": 64},
    "output_tokens": {"mean": 58.45, "sigma": 0.9, "min": 1, "max": 320},
}
CONFIG = {"model": {"token_ids_below": 1000}, "serving": {"block_size": 16}}


def test_open_loop_is_the_same_for_every_seed():
    a = loadgen.OpenLoop(100.0, 5.0, seed=7)
    b = loadgen.OpenLoop(100.0, 5.0, seed=7)
    c = loadgen.OpenLoop(100.0, 5.0, seed=2**40 + 7)
    assert np.array_equal(a.due, b.due)
    gaps = np.diff(a.due, prepend=0.0)
    gaps_c = np.diff(c.due, prepend=0.0)
    # the same gaps in another order
    assert np.allclose(np.sort(gaps), np.sort(gaps_c))
    assert not np.allclose(gaps, gaps_c)
    assert not np.all(np.diff(gaps) >= 0)  # shuffled, not sorted
    assert len(a) == 500 and a.due[-1] == pytest.approx(5.0)
    assert gaps.mean() == pytest.approx(0.01)


def test_lag_counts_from_the_due_time():
    sched = loadgen.OpenLoop(10.0, 1.0, seed=3)
    due = sched.due_now(0.5)
    assert [d for _, d in due] == sorted(d for _, d in due)
    assert all(d <= 0.5 for _, d in due)
    for i, d in due:
        sched.sent(i, 0.5)
    assert sched.lag_s == pytest.approx([0.5 - d for _, d in due])
    assert sched.next_due() > 0.5


def test_closed_loop_staggers_and_resends_on_completion():
    cl = loadgen.ClosedLoop(3, 1.5, n_requests=6)
    assert cl.due_now(0.0) == [(0, 0.0)]
    assert cl.due_now(1.2) == [(1, 0.5), (2, 1.0)]
    assert cl.next_due() is None
    cl.completed(1, 2.0)
    assert cl.due_now(1.9) == []
    assert cl.due_now(2.0) == [(3, 2.0)]
    cl.completed(0, 2.5)
    cl.completed(2, 2.5)
    assert cl.due_now(3.0) == [(4, 2.5), (5, 2.5)]
    cl.completed(3, 3.5)
    with pytest.raises(RuntimeError):
        cl.due_now(4.0)


def test_llm_requests_same_sizes_for_every_seed():
    p1, o1 = llm_paged.requests(CONFIG, TRAFFIC, seed=5)
    p1b, o1b = llm_paged.requests(CONFIG, TRAFFIC, seed=5)
    p2, o2 = llm_paged.requests(CONFIG, TRAFFIC, seed=2**33 + 5)
    assert o1 == o1b and all(np.array_equal(a, b) for a, b in zip(p1, p1b))
    # the same sizes in the same order for every seed; other token ids
    assert o1 == o2 and list(map(len, p1)) == list(map(len, p2))
    assert not all(np.array_equal(a, b) for a, b in zip(p1, p2))
    for g in range(0, 64, 16):  # each group of 16 holds the same sizes
        assert sorted(o1[g:g + 16]) == sorted(o1[:16])
        assert sorted(map(len, p1[g:g + 16])) == sorted(map(len, p1[:16]))
    assert o1[:16] != sorted(o1[:16])
    assert all(1 <= len(p) <= 64 for p in p1)
    assert all(1 <= o <= 320 for o in o1)
    assert max(int(p.max()) for p in p1) < 1000
    # a group of 16 mid-quantiles comes near the published means
    assert 15 < np.mean([len(p) for p in p1[:16]]) < 20
    assert 45 < np.mean(o1[:16]) < 60
    shape = llm_paged.pool_shape(CONFIG, TRAFFIC)
    assert shape == {"slots": 6, "max_prompt_len": 64, "max_len": 400,
                     "block_size": 16}


def test_payloads_repeat_from_the_seed():
    a = fixedpoint.payload_bank(160, 4, seed=9, share=1 / 16)
    assert np.array_equal(a, fixedpoint.payload_bank(160, 4, seed=9, share=1 / 16))
    assert np.allclose(a.sum(axis=1), 1.0, atol=1e-5)
    # each vector is uniform over a seed set of n / 16 vertices of its own
    assert ((a > 0).sum(axis=1) == 10).all()
    assert np.allclose(a[a > 0], 0.1)
    assert not np.array_equal(a[0] > 0, a[1] > 0)
    other = fixedpoint.payload_bank(160, 4, seed=2**40 + 9, share=1 / 16)
    assert not np.array_equal(a, other)


class SlowEngine:
    """A stand-in engine whose every step stalls for ``dt`` seconds and
    completes every request it holds."""

    def __init__(self, dt):
        self.dt, self.queue, self.results, self.tick = dt, [], {}, 0
        self.slot_req, self._new_tokens = [None], np.zeros(1, np.int32)
        self.active = np.zeros(1, bool)

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        time.sleep(self.dt)
        for r in self.queue:
            self.results[r.id] = types.SimpleNamespace(n_tokens=1, converged=True)
        self.queue, self.tick = [], self.tick + 1


def test_a_stall_is_charged_to_the_requests_behind_it():
    sched = loadgen.OpenLoop(200.0, 0.3, seed=4)
    eng = SlowEngine(0.05)
    recs, t0, t_end, _ = serve_loop.drive(
        eng, sched, lambda i: types.SimpleNamespace(id=i), 0.3, needs_done=True)
    assert len(recs) == len(sched)
    for r in recs.values():
        assert r.sent >= r.due - 1e-9
        assert r.done - r.due >= r.done - r.sent
    # requests due during a 50 ms step wait for it: lag grows past 10 ms
    assert max(sched.lag_s) > 0.01
    assert len(sched.lag_s) == len(recs)
