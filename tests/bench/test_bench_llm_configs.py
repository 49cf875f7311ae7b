"""An LLM configuration brings its own reference module and counts
(``"reference"`` -> ``bench/reference/<name>.py``): the dense counts taken
through that lookup are ``bench/flops.py``'s, and a second LLM
configuration joins a throwaway benchmark by new files and list entries
alone, with each tiny cell reporting exactly its own metrics."""

import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import pytest  # noqa: E402

import cells  # noqa: E402
from bench import flops, spec  # noqa: E402
from bench.serve_loop import Step, Tracing  # noqa: E402

ROOT = cells.ROOT
SEED = 2**33 + 21
LLM_E2E = ("llm_tok_s", "tpot_p95_ms", "ttft_p95_ms")
PEAKS = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e3}
# a throwaway architecture: the dense reference's equations, and counts of
# its own (three times the dense ones) that the readers must use
PROBE_REFERENCE = '''
from bench.reference import dense

program_config = dense.program_config
make_weights = dense.make_weights
served_gaps = dense.served_gaps
control = dense.control


def span_flops(m, start, stop, *, head_from, run=None):
    return 3 * dense.span_flops(m, start, stop, head_from=head_from)


def decode_bytes(m, ticks, positions, run=None):
    return 3 * dense.decode_bytes(m, ticks, positions)
'''


def test_minicpm_counts_through_the_reference_lookup_are_the_dense_formulas():
    c = json.loads((ROOT / "bench/configs/minicpm-2b.json").read_text())
    m = c["model"]
    ref = spec.reference(c)
    assert Path(ref.__file__) == ROOT / "bench/reference/dense.py"
    for start, stop, head_from in [(0, 19, 18), (19, 76, 0), (0, 64, 63), (5, 5, 0)]:
        assert ref.span_flops(m, start, stop, head_from=head_from) == flops.span_flops(
            m, start, stop, head_from=head_from)
    assert flops.weight_bytes(m) == 5_450_347_008  # 2.725e9 parameters in bf16
    assert flops.kv_bytes_per_token(m) == 368_640  # 0.352 MiB
    assert ref.decode_bytes(m, 1, 0) == flops.weight_bytes(m)
    assert ref.decode_bytes(m, 0, 1) == flops.kv_bytes_per_token(m)
    assert ref.decode_bytes(m, 16, 2400) == (
        16 * flops.weight_bytes(m) + 2400 * flops.kv_bytes_per_token(m))
    kw = ref.program_config(c)
    assert kw["family"] == "dense" and kw["n_kv_heads"] == 36


def test_a_configuration_without_a_reference_has_none():
    c = json.loads((ROOT / "bench/configs/minicpm-2b.json").read_text())
    del c["reference"]
    with pytest.raises(KeyError):
        spec.reference(c)


def _real_with_a_probe_cell(real: Path) -> None:
    """The real benchmark in ``real`` with one more LLM cell, whose name
    says nothing of its model, appended to every LLM metric list."""
    s = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench/configs", real / "bench/configs")
    c = json.loads((ROOT / "bench/configs/minicpm-2b.json").read_text())
    c.update(name="probe-lm", reference="probe")
    (real / "bench/configs/probe-lm.json").write_text(json.dumps(c))
    s["configs"].append({"name": "probe-lm", "source": "test",
                         "file": "bench/configs/probe-lm.json", "reduced": [],
                         "why": "probe"})
    s["workloads"].append({"name": "probe.chat", "config": "probe-lm",
                           "traffic": "alpaca", "chips": 1, "why": "probe"})
    for m in s["end_to_end"]:
        if m["name"] in LLM_E2E:
            m["workloads"].append("probe.chat")
    (real / "BENCHMARK.json").write_text(json.dumps(s))


def _add_probe_config(bench: Path) -> None:
    """A second tiny LLM configuration, backed by a throwaway reference
    module, joins the tiny benchmark: new files and list entries only."""
    (bench / "bench/reference/probe.py").write_text(PROBE_REFERENCE)
    c = json.loads((bench / "bench/configs/tiny-dense.json").read_text())
    c.update(name="tiny-probe", reference="probe")
    (bench / "bench/configs/tiny-probe.json").write_text(json.dumps(c))
    s = json.loads((bench / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny-probe", "source": "test",
                         "file": "bench/configs/tiny-probe.json", "reduced": [],
                         "why": "smoke"})
    s["workloads"].append({"name": "tiny.probe", "config": "tiny-probe",
                           "traffic": "tiny_chat", "chips": 1, "why": "smoke"})
    for m in s["end_to_end"] + s["per_layer"]:
        if "tiny.chat" in m.get("workloads", ()):
            m["workloads"].append("tiny.probe")
    (bench / "BENCHMARK.json").write_text(json.dumps(s))


def test_a_second_llm_configuration_joins_by_files_and_list_entries(tmp_path, monkeypatch):
    from bench.kinds import llm_paged

    real = tmp_path / "real"
    _real_with_a_probe_cell(real)
    bench = cells.make_bench(tmp_path / "tiny", real=real)
    tiny = json.loads((bench / "BENCHMARK.json").read_text())
    lists = {m["name"]: m.get("workloads") for m in tiny["end_to_end"]}
    assert lists["llm_tok_s"] == ["tiny.chat"]
    assert lists["solve_p50_ms"] == ["tiny.ppr"]

    out = cells.run(bench, "tiny.ppr", SEED)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"solve_p50_ms", "setup_s"}

    _add_probe_config(bench)
    runs = []
    drive = llm_paged.run
    monkeypatch.setattr(llm_paged, "run", lambda *a, **k: runs.append(drive(*a, **k)) or runs[-1])
    out = cells.run(bench, "tiny.probe", SEED + 1)
    assert out["correct"] is True
    assert set(out["metrics"]) == {*LLM_E2E, "setup_s"}

    # the per-layer counts come from the probe's module, not from dense
    run = runs[-1]
    assert Path(run.reference.__file__) == bench / "bench/reference/probe.py"
    assert run.config["reference"] == "probe"
    tr = Tracing(0, 0)
    tr.window = (run.t0, run.t_end)
    tr.steps = [Step(run.t0, run.t_end, 40)]
    run.tracing, run.peaks = tr, PEAKS
    run.trace_summary = {"window_s": 1.0, "busy_s": 1.0, "program_s": {"_fused_loop": 1.0}}
    got = {m: spec.reader(m)(run) for m in ("llm_mfu", "decode_hbm_roofline")}
    run.reference = spec.reference({"reference": "dense"})
    dense = {m: spec.reader(m)(run) for m in got}
    for m in got:
        assert dense[m] > 0
        assert got[m] == pytest.approx(3 * dense[m], rel=1e-12)
    # no fallback: a run that lost its reference module fails, not silences
    run.reference = None
    for m in got:
        with pytest.raises(AttributeError):
            spec.reader(m)(run)
