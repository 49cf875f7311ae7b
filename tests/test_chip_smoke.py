"""CPU rehearsal of ``chip_smoke.py``: the same phases, control flow and
checks at the smoke config (the script itself refuses to run without a TPU).
"""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_compile_cache_location(monkeypatch, tmp_path):
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        # set outside: JAX reads the variable itself, the code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        # unset: a fixed directory inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compilation_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_refuses_to_run_without_a_tpu(capsys):
    cs = _load()
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_at_smoke_size(capsys):
    cs = _load()
    cs.run(cs.SMOKE, seed=0, chips=1)
    out = capsys.readouterr().out
    for phase in ("phase 1", "phase 2", "phase 3"):
        assert f"# {phase}" in out
    assert "requests certified" in out


_FOUR_CHIPS = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", {path!r})
    cs = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = cs
    spec.loader.exec_module(cs)
    cs.run(cs.SMOKE, seed=0, chips=4)
    print("FOUR-CHIP-PHASE-DONE")
    """
)


@pytest.mark.slow
def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS.format(path=str(ROOT / "chip_smoke.py"))],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-5000:]}"
    )
    assert "FOUR-CHIP-PHASE-DONE" in proc.stdout
