"""Computations shared by the metric readers in ``bench/metrics``.

Each reader is ``read(run) -> float | None`` over a
:class:`bench.kinds.RunData`; None means the run holds nothing for it to
read, and the metric is left out of the result line.
"""

from __future__ import annotations

from typing import List, Optional

from bench.stats import percentile


def waits_ms(run, until) -> List[float]:
    """``until(rec) - rec.due`` in ms for every request sent in the window;
    a request that never got there counts as waiting until the end of the
    drain (a lower bound on its wait)."""
    out = []
    for r in run.in_window():
        t = until(r)
        out.append(((t if t is not None else run.t_drained) - r.due) * 1e3)
    return out


def solve_waits_ms(run) -> List[float]:
    """Due time to certified solution, per request due in the window."""
    return waits_ms(run, lambda r: r.done if r.result is not None
                    and r.result.converged else None)


def p95(values) -> Optional[float]:
    return percentile(values, 95)


def tokens_in_window(run) -> int:
    n = 0
    for r in run.recs.values():
        got = [k for t, k in r.deliveries if t <= run.t_end]
        n += got[-1] if got else 0
    return n


def tpot_ms(run) -> List[float]:
    """Per request with deliveries at two or more times in the window: the
    time from its first delivery to its last over the tokens delivered
    after the first delivery."""
    out = []
    for r in run.recs.values():
        d = [(t, k) for t, k in r.deliveries if t <= run.t_end]
        if len(d) >= 2:
            (t1, k1), (t2, k2) = d[0], d[-1]
            out.append((t2 - t1) / (k2 - k1) * 1e3)
    return out


def traced_window(run):
    tr = run.tracing
    if tr is None or tr.window is None or tr.window[1] is None:
        return None
    return tr.window


def idle_share(run) -> Optional[float]:
    s = run.trace_summary
    if not s or s["window_s"] <= 0 or s["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def program_s(run, name: str) -> Optional[float]:
    s = run.trace_summary
    if not s:
        return None
    return s["program_s"].get(name)


def traced_ticks(run) -> int:
    return sum(st.ticks for st in run.tracing.steps) if run.tracing else 0


def decode_positions(run, lo: float, hi: float) -> int:
    """Positions attended by the decode ticks whose tokens were delivered
    in ``(lo, hi]``: output token ``j >= 1`` of a prompt of ``p`` tokens
    comes from the tick that attends ``p + j`` positions."""
    tot = 0
    for r in run.recs.values():
        p = len(r.req.prompt)
        prev = 0
        for t, k in r.deliveries:
            if lo < t <= hi:
                a = max(prev, 1)
                # sum of (p + j) for j in [a, k)
                tot += (k - a) * p + (a + k - 1) * (k - a) // 2
            prev = k
    return tot


def model_flops_in_window(run) -> float:
    """Operations the model needs for the real tokens of the window:
    prompts admitted (first delivery) in it and output tokens delivered in
    it, padding excluded, as the configuration's reference module counts
    them."""
    span_flops = run.reference.span_flops
    m = run.config["model"]
    tot = 0.0
    for r in run.recs.values():
        p = len(r.req.prompt)
        d = [(t, k) for t, k in r.deliveries if t <= run.t_end]
        if not d:
            continue
        tot += span_flops(m, 0, p, head_from=p - 1, run=run)
        k = d[-1][1]
        tot += span_flops(m, p, p + k - 1, head_from=0, run=run)
    return tot


def share_of_window(run, name: str) -> Optional[float]:
    t = program_s(run, name)
    s = run.trace_summary
    if t is None or not s or s["window_s"] <= 0:
        return None
    return 100.0 * t / s["window_s"]


def obs_span_s(run, name: str) -> Optional[float]:
    """Seconds of the program's ``obs`` spans called ``name`` inside the
    traced window (their clock is the host clock of the window)."""
    win = traced_window(run)
    if win is None or not run.tracing.obs_spans:
        return None
    lo, hi = win[0] * 1e9, win[1] * 1e9
    tot = 0.0
    for ev in run.tracing.obs_spans:
        ph, nm, ts, dur = ev[0], ev[1], ev[2], ev[3]
        if ph == "X" and nm == name:
            tot += max(0.0, min(ts + dur, hi) - max(ts, lo))
    return tot / 1e9
