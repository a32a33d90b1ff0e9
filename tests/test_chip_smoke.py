"""chip_smoke.py off the chip: its CPU rehearsal passes every check and
never claims a chip run, its device guard refuses anything but a TPU, and
the compile-cache helper places the cache as documented."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.launch import runtime

REPO = Path(__file__).resolve().parents[1]
OK_MARK = '"ok": true'


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module      # dataclasses look it up here
    spec.loader.exec_module(module)
    return module


def test_cpu_rehearsal_passes_every_check(chip_smoke, capsys, monkeypatch,
                                          tmp_path):
    # a set cache variable keeps main() from turning the cache on in
    # this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = chip_smoke.main(["--cpu-rehearsal"])
    out = capsys.readouterr().out
    assert rc == 0, out
    verdicts = [line for line in out.splitlines()
                if ": PASS: " in line or ": FAIL: " in line]
    assert len(verdicts) == 9, out          # 3 train + 6 serve checks
    assert all(": PASS: " in line for line in verdicts), out
    assert OK_MARK not in out


@pytest.mark.parametrize("phase", ["train_phase", "serve_phase"])
def test_rehearsal_phase_checks(chip_smoke, phase):
    result = getattr(chip_smoke, phase)(chip_smoke.REHEARSAL)
    assert result["checks"] and all(result["checks"].values()), \
        result["checks"]


def test_device_guard(chip_smoke):
    assert chip_smoke.tpu_refusal(jax.devices()) is not None
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert chip_smoke.tpu_refusal([tpu]) is None


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_tpu_or_repo(where, tmp_path):
    """Under JAX_PLATFORMS=cpu, or in a directory holding only the
    script, it exits nonzero before any phase and prints no verdict."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert OK_MARK not in proc.stdout
    assert "[smoke]" not in proc.stdout


def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = runtime.enable_compile_cache()
        assert runtime.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert Path(first) == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
