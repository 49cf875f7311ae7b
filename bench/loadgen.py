"""Load generation: request sets drawn from ``--seed`` and the host-clock
schedules that offer them.

Every seed gets the *same set* of sizes and arrival gaps: lengths are the
quantiles of the traffic file's distribution, gaps those of an exponential.
Lengths drawn afresh per seed would change the work itself (measured: 8-12%
between seeds against nearly nothing between two runs of one seed).  The
seed shuffles the gaps of an open loop, whose window holds thousands of
requests, and draws token ids and payloads; a closed loop's lengths keep
one order (:func:`bench.kinds.llm_paged.requests`).

Two ways of offering load:

- :class:`OpenLoop`: request ``i`` is due at ``t0 + due[i]`` on the host
  clock whatever the system does; latency counts from the due time, so a
  stalled system charges its stall to every request behind it, and the lag
  of each submission behind its due time is recorded.
- :class:`ClosedLoop`: ``clients`` callers, each sending its next request
  when the previous one completes; client ``c`` sends its first at
  ``t0 + c * ramp_s / clients`` so the pool starts staggered.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of ``seed`` (any size of int)."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles ``(i + 0.5) / n`` of a lognormal
    with the given ``mean`` and ``sigma``, clipped to ``[min, max]``."""
    nd = NormalDist()
    mu = math.log(dist["mean"]) - dist["sigma"] ** 2 / 2
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(mu + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of an exponential, scaled so that
    they sum to ``total_s`` (a Poisson process of rate ``n / total_s``)."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (total_s / g.sum())


class OpenLoop:
    """Due times of ``n`` requests at ``rate`` per second over ``seconds``:
    the same gaps for every seed, in the seed's order."""

    def __init__(self, rate: float, seconds: float, seed: int):
        n = max(1, int(round(rate * seconds)))
        gaps = exponential_gaps(n, seconds)
        rng_for(seed, 1).shuffle(gaps)
        self.due = np.cumsum(gaps)  # seconds after t0; the last is `seconds`
        self.next = 0
        self.lag_s: List[float] = []

    def __len__(self) -> int:
        return len(self.due)

    def due_now(self, elapsed: float) -> List[tuple]:
        """``(index, due time)`` of requests due by ``elapsed`` seconds and
        not yet sent."""
        out = []
        while self.next < len(self.due) and self.due[self.next] <= elapsed:
            out.append((self.next, float(self.due[self.next])))
            self.next += 1
        return out

    def sent(self, i: int, elapsed: float) -> None:
        self.lag_s.append(max(0.0, elapsed - float(self.due[i])))

    def next_due(self) -> Optional[float]:
        return float(self.due[self.next]) if self.next < len(self.due) else None


class ClosedLoop:
    """``clients`` callers drawing requests ``0, 1, 2, ...`` in turn."""

    def __init__(self, clients: int, ramp_s: float, n_requests: int):
        self.clients = clients
        self.n_requests = n_requests
        self.ready_at: Dict[int, float] = {
            c: c * ramp_s / clients for c in range(clients)
        }  # client -> when its next request is due (seconds after t0)
        self.client_of: Dict[int, int] = {}
        self.next = 0

    def due_now(self, elapsed: float) -> List[tuple]:
        """``(request index, due time)`` of every client ready to send."""
        out = []
        for c in sorted(self.ready_at, key=lambda c: (self.ready_at[c], c)):
            due = self.ready_at[c]
            if due > elapsed:
                break
            if self.next >= self.n_requests:
                raise RuntimeError(
                    f"closed loop ran out of its {self.n_requests} requests"
                )
            del self.ready_at[c]
            self.client_of[self.next] = c
            out.append((self.next, due))
            self.next += 1
        return out

    def completed(self, i: int, elapsed: float) -> None:
        """Request ``i`` completed at ``elapsed``: its client sends again."""
        self.ready_at[self.client_of.pop(i)] = elapsed

    def next_due(self) -> Optional[float]:
        return min(self.ready_at.values()) if self.ready_at else None
