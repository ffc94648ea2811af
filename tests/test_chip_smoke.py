"""``chip_smoke.py`` on the CPU: its phases at tiny size (control flow
only; the chip run is the real check), its platform gate, and the
compile-cache placement every entry point shares."""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra)
    return env


def test_platform_gate_raises_without_tpu(smoke):
    with pytest.raises(smoke.NoTPUError, match="no TPU"):
        smoke.require_tpu()


def test_script_fails_without_tpu_and_prints_no_result():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=_env(), cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_one_chip_phase_tiny(smoke):
    check = smoke.one_chip_phase("tiny", epochs=2, interpret=True)
    assert check.failed == []


def test_four_chip_phase_tiny():
    """Four emulated CPU devices in a child: the device count locks at
    the first JAX backend use, and this process already has one."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke;"
            " c = chip_smoke.four_chip_phase('tiny', epochs=2,"
            " interpret=True); sys.exit(1 if c.failed else 0)")
    from repro.dist.mesh import host_device_flags
    flags = host_device_flags(4, os.environ.get("XLA_FLAGS", ""))
    r = subprocess.run([sys.executable, "-c", code, ROOT],
                       capture_output=True, text=True, timeout=300,
                       env=_env(XLA_FLAGS=flags), cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "check PASS: device pull lanes == host cache misses" in r.stdout


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    from repro.compile_cache import compile_cache_dir, enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)


def test_compile_cache_dir_defaults_to_repo(monkeypatch):
    import jax

    from repro.compile_cache import compile_cache_dir, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache_dir() == want
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
