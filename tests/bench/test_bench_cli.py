"""The command refuses to measure where it cannot: without a TPU, and in a
directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def _run(cwd: Path, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_no_tpu_no_result():
    p = _run(ROOT, "pagerank.ppr")
    assert p.returncode == 2, p.stderr[-2000:]
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_no_result(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in spec["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "minicpm2b.alpaca")
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_unknown_chip_is_an_error():
    import pytest

    from bench.peaks import peaks

    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v4")
