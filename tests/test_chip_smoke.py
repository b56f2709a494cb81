"""``chip_smoke.py`` off the chip, and where entry points put the compile
cache.  The smoke script must never report success without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.launch.compile_cache import (  # noqa: E402
    CHECKOUT_CACHE_DIR,
    enable_compile_cache,
)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_no_tpu_exits_nonzero_without_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_cpu_rehearsal_runs_every_phase_then_fails(capsys):
    assert chip_smoke.main(["--agents", "256", "--steps", "2"]) == 1
    out = capsys.readouterr().out
    assert "main: OK" in out and "parity: OK" in out
    assert '"ok"' not in out


@pytest.mark.subprocess
def test_cpu_rehearsal_four_chips_then_fails(tmp_path):
    """The --four-chips phase on four host devices: the distributed run,
    the halo comparison (ghost readers included) and the substance
    comparison all pass, and still no result line off the TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--four-chips",
         "--agents", "2048"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "four-chips: OK" in proc.stdout, proc.stdout + proc.stderr
    assert "halo: int16 wire" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_env_var_wins(monkeypatch, restore_cache_dir, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_compile_cache_defaults_into_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    assert os.path.dirname(CHECKOUT_CACHE_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
