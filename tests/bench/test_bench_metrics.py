"""FLOP and byte functions, and every metric reader, against hand-computed
values on fixed records, steps and trace summaries."""

import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import flops, spec  # noqa: E402
from bench.kinds import RunData  # noqa: E402
from bench.serve_loop import Rec, Step, Tracing  # noqa: E402
from bench.stats import percentile  # noqa: E402

M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
     "d_ff": 16, "vocab": 32, "tie_embeddings": True, "dtype_bytes": 2}
PEAKS = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e3}


def test_parameter_and_byte_counts():
    # attn: 8*(2+2)*4 + 2*4*8 = 192; mlp 3*8*16 = 384 -> 576 per layer
    assert flops.layer_matmul_params(M) == 576
    # 2 * (576 + 16) + 8 + 32*8 = 1448
    assert flops.n_params(M) == 1448
    assert flops.weight_bytes(M) == 2896
    assert flops.kv_bytes_per_token(M) == 2 * 2 * 1 * 4 * 2
    assert flops.operator_bytes(3) == 36.0


def test_token_flops_and_closed_form():
    # 2*2*576 + 4*2*2*4*(p+1) (+ 2*8*32 with the head)
    assert flops.token_flops(M, 0, head=False) == 2304 + 64
    assert flops.token_flops(M, 4, head=True) == 2304 + 64 * 5 + 512
    want = sum(flops.token_flops(M, p, head=p >= 5) for p in range(3, 9))
    assert flops.span_flops(M, 3, 9, head_from=5) == pytest.approx(want)
    assert flops.span_flops(M, 4, 4, head_from=0) == 0.0


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95
    assert percentile([3.0], 95) == 3.0
    assert percentile([1, 2], 50) == 1
    assert percentile([], 95) is None


def _rec(i, prompt_len, due, sent, deliveries, done=None, result=None):
    r = Rec(i, types.SimpleNamespace(prompt=np.zeros(prompt_len)), due, sent)
    r.deliveries = list(deliveries)
    r.done, r.result = done, result
    return r


def _run(recs, **kw):
    base = dict(config={"model": M}, traffic={}, seconds=10.0, setup_s=7.5,
                t0=100.0, t_end=110.0, t_drained=112.0, recs=recs,
                memory_peak_bytes=1, correct=True, attempted=len(recs),
                failed=0, checks={}, peaks=PEAKS)
    base.update(kw)
    return RunData(**base)


def read(name, run):
    return spec.reader(name)(run)


@pytest.fixture
def llm_run():
    recs = {
        # prompt 4: tokens 5 at 101, 9 at 103, 12 at 111 (after the window)
        0: _rec(0, 4, due=100.0, sent=100.0,
                deliveries=[(101.0, 5), (103.0, 9), (111.0, 12)]),
        # prompt 2: 1 token at 104.5, then nothing (one delivery)
        1: _rec(1, 2, due=104.0, sent=104.2, deliveries=[(104.5, 1)]),
        # sent in the window, no first token before the drain ended
        2: _rec(2, 3, due=109.0, sent=109.0, deliveries=[]),
    }
    tr = Tracing(0, 0)
    tr.window = (102.0, 106.0)
    tr.steps = [Step(102.0, 103.0, 4), Step(103.5, 104.5, 2)]
    tr.obs_spans = []
    summary = {"window_s": 4.0, "busy_s": 3.0,
               "program_s": {"_fused_loop": 2.0, "_admit": 0.5}}
    return _run(recs, tracing=tr, trace_summary=summary,
                reference=spec.reference({"reference": "dense"}))


def test_llm_end_to_end_readers(llm_run):
    assert read("setup_s", llm_run) == 7.5
    # 9 + 1 tokens by the window's end over 10 s
    assert read("llm_tok_s", llm_run) == pytest.approx(1.0)
    # first delivery - due: 1000 ms, 500 ms, and the drain's end: 3000 ms
    assert read("ttft_p95_ms", llm_run) == pytest.approx(3000.0)
    # only request 0 has two deliveries in the window: (103 - 101) / 4
    assert read("tpot_p95_ms", llm_run) == pytest.approx(500.0)


def test_llm_per_layer_readers(llm_run):
    assert read("idle_share.llm", llm_run) == pytest.approx(25.0)
    assert read("prefill_share", llm_run) == pytest.approx(12.5)
    # decode tokens delivered in (102, 106]: request 0's tokens 5..8 at 103
    # attend 4+5 .. 4+8 positions (9+10+11+12 = 42); request 1's only token
    # comes from its admission (no decode tick)
    kv = flops.kv_bytes_per_token(M) * 42
    want = 100.0 * (flops.weight_bytes(M) * 6 + kv) / 1e3 / 2.0
    assert read("decode_hbm_roofline", llm_run) == pytest.approx(want)
    # request 0: prompt 4 (head at 3) + tokens 1..8 at positions 4..11;
    # request 1: prompt 2 (head at 1), nothing decoded
    f = (flops.span_flops(M, 0, 4, head_from=3) + flops.span_flops(M, 4, 12, head_from=0)
         + flops.span_flops(M, 0, 2, head_from=1))
    assert read("llm_mfu", llm_run) == pytest.approx(100.0 * f / 10.0 / 1e6)


def test_prefill_share_is_zero_in_a_trace_without_admissions(llm_run):
    llm_run.trace_summary["program_s"].pop("_admit")
    assert read("prefill_share", llm_run) == 0.0


def test_readers_find_nothing_without_a_trace(llm_run):
    llm_run.tracing, llm_run.trace_summary = None, None
    for name in ("idle_share.llm", "decode_hbm_roofline", "prefill_share",
                 "operator_hbm_roofline", "host_share.ppr"):
        assert read(name, llm_run) is None


def test_fixed_point_readers():
    ok = types.SimpleNamespace(converged=True, admit_tick=3, retire_tick=53)
    ok2 = types.SimpleNamespace(converged=True, admit_tick=10, retire_tick=40)
    recs = {
        0: _rec(0, 0, due=101.0, sent=101.001, deliveries=[], done=101.2, result=ok),
        1: _rec(1, 0, due=102.0, sent=102.003, deliveries=[], done=102.1, result=ok2),
        2: _rec(2, 0, due=109.0, sent=109.0, deliveries=[]),  # never certified
    }
    tr = Tracing(0, 0)
    tr.window = (102.0, 106.0)
    tr.steps = [Step(102.0, 102.5, 3), Step(103.0, 104.0, 5)]
    # serve.tick spans: 1 s inside the window, 0.5 s of another straddling it
    tr.obs_spans = [("X", "serve.tick", 103.0e9, 1.0e9, 0, {}),
                    ("X", "serve.tick", 105.5e9, 1.0e9, 0, {}),
                    ("X", "serve.admit", 103.0e9, 2.0e9, 0, {})]
    run = _run(recs, config={"n": 10}, tracing=tr, lag_s=[0.001, 0.003, 0.0],
               trace_summary={"window_s": 4.0, "busy_s": 1.0,
                              "program_s": {"_fused_loop": 0.5}})
    # 200, 100 and 3000 ms (drain end 112 - due 109)
    assert read("solve_p95_ms.ppr", run) == pytest.approx(3000.0)
    assert read("solve_p50_ms", run) == pytest.approx(200.0)
    assert read("ticks_per_solve", run) == pytest.approx(40.0)
    assert read("gen_lag_p99_ms", run) == pytest.approx(3.0)
    assert read("idle_share.ppr", run) == pytest.approx(75.0)
    assert read("host_share.ppr", run) == pytest.approx(100.0 * (1 - 1.5 / 4.0))
    assert read("operator_hbm_roofline", run) == pytest.approx(
        100.0 * 400.0 * 8 / 1e3 / 0.5)
