"""Plain float64 reference of the D-iteration PageRank fixed point
(arXiv:1301.3007, arXiv:1202.3108):

    x = damping * P x + (1 - damping) * v

with ``P`` column-stochastic: node ``j`` sends its mass equally to
``out_degree`` distinct random successors drawn from the seed, plus a ring
edge to ``j + 1`` (strong connectivity), never to itself.  The graph is
rebuilt here from the seed by the benchmark's own copy of the generator
and kept sparse; nothing of the program is used.
"""

from __future__ import annotations

import numpy as np


class PageRank:
    def __init__(self, n: int, *, damping: float, out_degree: int, seed: int):
        rng = np.random.default_rng(seed)
        rows, cols, vals = [], [], []
        for j in range(n):
            succ = set(rng.choice(n, size=min(out_degree, n), replace=False).tolist())
            succ.add((j + 1) % n)
            succ.discard(j)
            for i in sorted(succ):
                rows.append(i)
                cols.append(j)
                vals.append(damping / len(succ))
        self.n, self.damping = n, damping
        self.rows = np.asarray(rows, np.int64)
        self.cols = np.asarray(cols, np.int64)
        self.vals = np.asarray(vals, np.float64)

    def apply(self, x: np.ndarray) -> np.ndarray:
        """``damping * P x`` in float64."""
        x = np.asarray(x, np.float64)
        return np.bincount(self.rows, self.vals * x[self.cols], minlength=self.n)

    def residual(self, x, v) -> float:
        """``|| f(x) - x ||_inf`` in float64: the configuration's criterion."""
        x = np.asarray(x, np.float64)
        v = np.asarray(v, np.float64)
        return float(np.max(np.abs(self.apply(x) + (1.0 - self.damping) * v - x)))

    def solve_rounded(self, v, eps: float, max_iters: int, dtype) -> np.ndarray:
        """The iteration run in ``dtype`` (the lower-precision control):
        iterate and operator rounded to it, stopped where its own residual
        reaches ``eps`` or the rounded iterate stops moving."""
        rnd = lambda a: np.asarray(a, dtype).astype(np.float64)  # noqa: E731
        vals = rnd(self.vals)
        v = rnd(v)
        x = np.zeros(self.n)
        for _ in range(max_iters):
            y = np.bincount(self.rows, vals * x[self.cols], minlength=self.n)
            y = rnd(y + rnd((1.0 - self.damping) * v))
            step = float(np.max(np.abs(y - x)))
            x = y
            if step <= eps:
                break
        return x
