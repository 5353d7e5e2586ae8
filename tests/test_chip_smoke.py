"""``chip_smoke.py`` on the CPU: every phase at a reduced size with the Pallas
kernels in interpret mode (so the script's control flow and reference
comparisons are exercised on every change), the four-chip phase on four
virtual CPU devices, the refusal of a host without a TPU, and where the
compile cache lands."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def _assert_passed(info):
    failed = [c for c in info["checks"].items if not c[1]]
    assert not failed, failed
    assert info["checks"].items


@pytest.fixture(scope="module")
def data():
    return smoke.braille_data(num_ticks=64, samples_per_class=6, seed=0)


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "quant"])
def test_train_and_serve_phases(data, quantized):
    learner, info = smoke.train_phase(
        quantized, data, backend="kernel", batch=8, commits=1, end_s=2,
        seed=0,
    )
    assert info["backend"] == "kernel"
    assert learner.backend._stream == "dma"   # Braille's measured density
    _assert_passed(info)
    _assert_passed(smoke.serve_phase(learner, data, requests=3, sessions=2))


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "quant"])
def test_core_phase(quantized):
    info = smoke.core_phase(quantized, backend="kernel", num_ticks=8,
                            batch=4, seed=0)
    _assert_passed(info)


def test_checks_catch_a_mismatch():
    checks = smoke.Checks()
    checks.equal("bitwise", [1, 2, 3], [1, 2, 4])
    checks.close("float", [1.0, 2.0], [1.0, 2.5])
    assert not checks.ok
    assert [ok for _, ok, _ in checks.items] == [False, False]


def test_main_refuses_a_host_without_tpu(capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)] + (
            [env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_four_chip_phase_on_virtual_devices():
    """The ``--four-chips`` path and its one-device comparisons, rehearsed
    on four virtual CPU devices (a child process: the device count is fixed
    when JAX starts)."""
    code = (
        "import chip_smoke as s\n"
        "d = s.braille_data(num_ticks=64, samples_per_class=6, seed=0)\n"
        "info = s.four_chip_phase(d, backend='kernel', batch=8, commits=1,"
        " sessions=2, seed=0)\n"
        "bad = [c for c in info['checks'].items if not c[1]]\n"
        "assert info['checks'].ok and not bad, bad\n"
        "print('four-chip phase ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=900,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "four-chip phase ok" in proc.stdout


def test_compile_cache_lands_in_the_env_directory(tmp_path):
    code = (
        "import jax\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n"
    )
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_fixed_repo_directory(monkeypatch):
    from repro.launch.cache import REPO_CACHE_DIR, enable_compile_cache

    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
