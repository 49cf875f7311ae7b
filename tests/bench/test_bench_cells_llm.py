"""The LLM cell end to end on this host at smoke size: the result line,
a cell added by files alone, the faults that must turn ``correct`` false,
and the float8 control that must fail the limit."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return cells.make_bench(tmp_path_factory.mktemp("bench_llm"))


def test_result_line(bench_dir):
    out = cells.run(bench_dir, "tiny.chat", SEED)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 3
    assert set(out["metrics"]) == {"llm_tok_s", "tpot_p95_ms", "ttft_p95_ms", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["metrics"]["llm_tok_s"]["unit"] == "tokens/s"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    checks = out["checks"]
    assert checks["logit_gap_max"]["value"] <= checks["logit_gap_max"]["limit"]


def test_traced_run_reports_per_layer_metrics_found_by_name(bench_dir):
    out = cells.run(bench_dir, "tiny.chat", SEED + 1, trace=1)
    assert out["correct"] is True
    # the throwaway metric file is read; device metrics need a device trace
    assert out["metrics"]["deliveries_seen"]["value"] > 0
    assert "llm_tok_s" not in out["metrics"]
    assert "busy_s" in out["device"] and "window_s" in out["device"]


def _step_unchanged(self, params, wstate, active, tick):
    return wstate, wstate["tokens"], None


def _token_altered(self, params, wstate, active, tick):
    wstate = self.pool.device_step(params, wstate, active)
    tok = (wstate["tokens"] + 1) % self.cfg.vocab
    return {**wstate, "tokens": tok}, tok, None


def _half_the_slots(self, params, wstate, active, tick):
    keep = active & (jnp.arange(active.shape[0]) % 2 == 0)
    new = self.pool.device_step(params, wstate, keep)
    return new, new["tokens"], None


@pytest.mark.parametrize("fault", [_step_unchanged, _token_altered, _half_the_slots],
                         ids=["state_unchanged", "token_altered", "half_the_slots"])
def test_faults_turn_correct_false(bench_dir, monkeypatch, fault):
    from repro.serving.workloads import PagedLLMWorkload

    monkeypatch.setattr(PagedLLMWorkload, "device_step", fault)
    out = cells.run(bench_dir, "tiny.chat", SEED + 2)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_float8_control_fails_the_limit(bench_dir):
    import jax

    from bench import calibrate, spec
    from bench.kinds import llm_paged

    cell = spec.load_cell("tiny.chat", bench_dir, bench_dir / "bench")
    limit = cell.config["limits"]["logit_gap"]
    for seed in (SEED, SEED + 3, SEED + 4):
        run = llm_paged.run(cell, seed, 1.0, False, devices=jax.devices(),
                            since_start=lambda: 0.0)
        assert run.correct
        reading = calibrate.control_reading(cell, run, seed)
        assert reading["control_logit_gap_max"] > limit
