"""Readers of what the program records about itself (``bench/program.py``):
queue wait from the engine's request stamps, and device idle under the
program's spans on the trace's clock."""

import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

import cells  # noqa: E402
from bench import program, spec, trace  # noqa: E402
from bench.kinds import RunData  # noqa: E402
from bench.serve_loop import Rec, Tracing  # noqa: E402


def _req(submit, admit=None):
    r = types.SimpleNamespace(_t_submit=submit)
    if admit is not None:
        r._t_admit = admit
    return r


def _run(recs, tracing=None, summary=None):
    return RunData(config={}, traffic={}, seconds=10.0, setup_s=1.0, t0=100.0,
                   t_end=110.0, t_drained=112.0, recs=recs, memory_peak_bytes=1,
                   correct=True, attempted=len(recs), failed=0, checks={},
                   tracing=tracing, trace_summary=summary)


def _queue_run():
    return _run({
        # admitted 20 ms and 300 ms after submission
        0: Rec(0, _req(100.0, 100.02), due=100.0, sent=100.0),
        1: Rec(1, _req(104.0, 104.3), due=104.0, sent=104.0),
        # never admitted: waits until the drain's end (112 - 109.5)
        2: Rec(2, _req(109.5), due=109.5, sent=109.5),
        # due after the window: not counted
        3: Rec(3, _req(110.5, 110.6), due=110.5, sent=110.5),
    })


@pytest.mark.parametrize("name", ["queue_wait_p95_ms.llm", "queue_wait_p95_ms.ppr"])
def test_queue_wait_readers(name):
    run = _queue_run()
    assert program.queue_waits_ms(run) == pytest.approx([20.0, 300.0, 2500.0])
    assert spec.reader(name)(run) == pytest.approx(2500.0)
    del run.recs[2]
    assert spec.reader(name)(run) == pytest.approx(300.0)


@pytest.mark.parametrize("name", ["queue_wait_p95_ms.llm", "queue_wait_p95_ms.ppr"])
def test_queue_wait_is_silent_without_admission_stamps(name):
    run = _run({0: Rec(0, types.SimpleNamespace(_t_submit=100.0), due=100.0,
                       sent=100.0)})
    assert program.queue_waits_ms(run) is None
    assert spec.reader(name)(run) is None


def _traced(host):
    # device busy [1000, 3000) and [6000, 7000) ns in a window [0, 10000)
    dev = trace.DeviceTrace([("p/a", 1000.0, 3000.0), ("p/b", 6000.0, 7000.0)], [])
    tr = Tracing(0, 0)
    tr.trace = trace.Trace({"/device:TPU:0": dev},
                           [("bench.window", 0.0, 10000.0)] + host)
    return _run({}, tracing=tr, summary={"window_s": 10e-6, "busy_s": 3e-6})


def test_engine_idle_share_counts_idle_under_program_spans():
    run = _traced([
        ("bench.step", 0.0, 9000.0),  # the harness's own span: not counted
        ("serve.admit", 500.0, 1500.0),  # idle [500, 1000)
        ("serve.retire", 3000.0, 5000.0),  # idle [3000, 5000)
        ("serve.ctrl", 4500.0, 6500.0),  # idle [5000, 6000) beyond the retire
    ])
    assert program.idle_under_spans_s(run) == pytest.approx(3.5e-6)
    assert spec.reader("engine_idle_share.ppr")(run) == pytest.approx(35.0)


def test_engine_idle_share_is_silent_without_program_spans():
    assert spec.reader("engine_idle_share.ppr")(_traced([("bench.step", 0.0, 9e3)])) is None
    assert spec.reader("engine_idle_share.ppr")(_run({})) is None


def test_overlap_of_interval_lists():
    assert program.overlap([(0, 2), (4, 6)], [(1, 5)]) == 2
    assert program.overlap([], [(1, 5)]) == 0


def test_traced_cell_reports_queue_wait(tmp_path):
    bench_dir = cells.make_bench(tmp_path)
    out = cells.run(bench_dir, "tiny.ppr", 2**32 + 11, trace=1)
    assert out["correct"] is True
    assert out["metrics"]["queue_wait_p95_ms.ppr"]["value"] >= 0
    # this host's profile has no device plane: nothing to set idle against
    assert "engine_idle_share.ppr" not in out["metrics"]
