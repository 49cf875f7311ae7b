"""The fixed-point cell end to end on this host at smoke size: the result
line, the faults that must turn ``correct`` false, and the bfloat16
control that must miss eps."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402

SEED = 2**32 + 5


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return cells.make_bench(tmp_path_factory.mktemp("bench_ppr"))


def test_result_line(bench_dir):
    out = cells.run(bench_dir, "tiny.ppr", SEED)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 25  # 20/s over 1.5 s, every one due is sent
    assert set(out["metrics"]) == {"solve_p95_ms", "setup_s"}
    res = out["checks"]["true_residual_max"]
    assert 0 < res["value"] < res["limit"] == 1e-6


def test_traced_run(bench_dir):
    out = cells.run(bench_dir, "tiny.ppr", SEED + 1, trace=1)
    assert out["correct"] is True
    got = out["metrics"]
    assert got["ticks_per_solve"]["value"] > 10
    assert 0 < got["host_share.ppr"]["value"] < 100
    assert "gen_lag_p99_ms" in got


def _step_unchanged(self, params, wstate, active, tick):
    _, residual = self.pool.device_step(wstate, active)
    return wstate, jnp.zeros((self.slots,), jnp.int32), residual


def _half_the_slots(self, params, wstate, active, tick):
    new, residual = self.pool.device_step(wstate, active)
    keep = (jnp.arange(self.slots) % 2 == 0)[:, None]
    x = jnp.where(keep, new["x"], wstate["x"])
    return {**new, "x": x}, jnp.zeros((self.slots,), jnp.int32), residual


def _answer_altered(self, slot):
    x = self.pool.solution(slot).copy()
    x[0] += 1e-3
    return x


@pytest.mark.parametrize("name", ["state_unchanged", "half_the_slots", "answer_altered"])
def test_faults_turn_correct_false(bench_dir, monkeypatch, name):
    from repro.serving.workloads import FixedPointWorkload

    if name == "answer_altered":
        monkeypatch.setattr(FixedPointWorkload, "output", _answer_altered)
    else:
        step = _step_unchanged if name == "state_unchanged" else _half_the_slots
        monkeypatch.setattr(FixedPointWorkload, "device_step", step)
    out = cells.run(bench_dir, "tiny.ppr", SEED + 2)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_bfloat16_control_misses_eps(bench_dir):
    import jax

    from bench import calibrate, spec
    from bench.kinds import fixedpoint

    cell = spec.load_cell("tiny.ppr", bench_dir, bench_dir / "bench")
    for seed in (SEED, SEED + 3, SEED + 4):
        run = fixedpoint.run(cell, seed, 0.5, False, devices=jax.devices(),
                             since_start=lambda: 0.0)
        assert run.correct
        got = calibrate.control_reading(cell, run, seed, requests=4)
        assert got["control_true_residual_max"] > cell.config["eps"]
    assert np.isfinite(got["control_true_residual_max"])
