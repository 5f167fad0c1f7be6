"""CPU-lane pins for the chip bring-up pieces: ``chip_smoke.py`` and
the two on-chip kernel tools refuse to run off a TPU, and the
compile-cache helper places the cache where
it says (``utils/env.configure_compile_cache``)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout         # names what it found
    assert "'cpu'" in out.stderr and "no CPU fallback" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("interpret", ["", "1"])
@pytest.mark.parametrize("tool", ["check_routed_kernels.py",
                                  "check_tpu_integration.py"])
def test_kernel_tools_refuse_cpu(tool, interpret):
    """On a CPU backend the kernels would run interpreted (or both twins
    resolve to segsum) and the tools would pass without meeting Mosaic."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="",
               LTPU_PALLAS_INTERPRET=interpret)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", tool)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert tool in out.stderr and "no CPU fallback" in out.stderr
    assert "PASS" not in out.stdout


def test_compile_cache_env_wins(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the code sets
    nothing."""
    import jax
    from lightgbm_tpu.utils.env import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_under_checkout(monkeypatch):
    import jax
    from lightgbm_tpu.utils.env import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = configure_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert configure_compile_cache() == first   # no pid, no time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
