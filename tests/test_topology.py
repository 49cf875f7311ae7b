"""Schedule structure + the paper's S2 cost claims (E1/E2)."""

import math

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import topology as T


@given(st.integers(min_value=1, max_value=4096))
def test_pivot(p):
    p0, mu0, extra = T.pivot(p)
    assert p0 == 2**mu0 <= p < 2 ** (mu0 + 1)
    assert extra == p - p0


@given(st.integers(min_value=1, max_value=257))
@settings(max_examples=60)
def test_paper_step_count(p):
    """E2: log2(p0)+2 steps; shifts vanish when p = 2^k (paper S4)."""
    sched = T.allreduce_schedule(p)
    assert len(sched) == T.paper_step_count(p)
    if T.is_power_of_two(p):
        assert all(st_.kind == "butterfly" for st_ in sched)
    else:
        assert sched[0].kind == "bshift" and sched[-1].kind == "fshift"


@given(st.integers(min_value=1, max_value=257))
@settings(max_examples=60)
def test_paper_message_count(p):
    """E1: p0*log2(p0) + 2(p - p0) messages per cycle."""
    assert T.schedule_messages(T.allreduce_schedule(p)) == T.paper_message_count(p)


@pytest.mark.parametrize("p", [2, 3, 4, 5, 7, 8, 12, 16, 24, 32])
def test_schedules_pair_validity(p):
    p0, _, extra = T.pivot(p)
    for sched in (
        T.allreduce_schedule(p),
        T.reduce_scatter_schedule(p),
        T.allgather_schedule(p),
    ):
        for stg in sched:
            srcs = [s for s, _ in stg.pairs]
            dsts = [d for _, d in stg.pairs]
            assert len(set(srcs)) == len(srcs), "duplicate sources"
            assert len(set(dsts)) == len(dsts), "duplicate destinations"
            assert all(0 <= r < p for r in srcs + dsts)
            if stg.kind in ("butterfly", "rs", "ag"):
                # butterfly pairs are symmetric (i <-> i^d)
                assert set(stg.pairs) == {(d, s) for s, d in stg.pairs}


@pytest.mark.parametrize("p", [5, 8, 12, 16, 24])
def test_rabenseifner_volume_beats_mrd_for_large_buffers(p):
    """The beyond-paper motivation: RS+AG moves ~2n per rank vs n*log2(p0).
    (Strict win requires log2(p0) >= 2; at p0 = 2 the two coincide.)"""
    n = 1 << 20
    mrd_vol = T.schedule_volume(T.allreduce_schedule(p), n)
    rab_vol = T.schedule_volume(T.rabenseifner_schedule(p), n)
    assert rab_vol < mrd_vol


@pytest.mark.parametrize("p", [4, 8, 16, 64])
def test_alpha_beta_model_crossover(p):
    """MRD (latency-optimal) wins small payloads; Rabenseifner wins large."""
    link = T.LinkModel.tpu_v5e_ici()
    small, large = 8, 1 << 28
    t_mrd_small = T.schedule_time(T.allreduce_schedule(p), small, link)
    t_rab_small = T.schedule_time(T.rabenseifner_schedule(p), small, link)
    assert t_mrd_small <= t_rab_small
    t_mrd_large = T.schedule_time(T.allreduce_schedule(p), large, link)
    t_rab_large = T.schedule_time(T.rabenseifner_schedule(p), large, link)
    assert t_rab_large < t_mrd_large


def test_volume_closed_form():
    # full-buffer stages: butterfly volume = p0*log2(p0)*n; shifts 2*extra*n
    for p in (5, 6, 7, 9, 16):
        p0, mu0, extra = T.pivot(p)
        n = 128
        vol = T.schedule_volume(T.allreduce_schedule(p), n)
        assert vol == (p0 * mu0 + 2 * extra) * n
