"""The platform decisions of `repro.platform`: interpret mode, the chip's
hardware constants, and where the persistent compile cache lives."""
import re

import jax
import pytest

from repro.core.memctrl import TPU_SPECS, TPUSpec
from repro.platform import CACHE_DIRNAME, device_spec, enable_compile_cache, interpret_mode


def test_interpret_mode_on_cpu():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True


def test_device_spec_off_tpu_is_the_modelled_chip():
    assert device_spec() == TPUSpec()


def test_device_spec_by_kind():
    assert device_spec("TPU v5 lite") is TPU_SPECS["TPU v5 lite"]


def test_device_spec_unknown_kind_raises():
    with pytest.raises(ValueError, match="TPU v99"):
        device_spec("TPU v99")


@pytest.fixture
def cache_dir_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_hlo_source_file_canonicalization_regex)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_hlo_source_file_canonicalization_regex", prev[1])


def test_compile_cache_honours_env(tmp_path, monkeypatch, cache_dir_config):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert enable_compile_cache(tmp_path / "checkout") == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir


def test_compile_cache_fixed_fallback(tmp_path, monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache(tmp_path)
    assert first == str(tmp_path.resolve() / CACHE_DIRNAME)
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache(tmp_path) == first  # same path every run


def test_compile_cache_strips_checkout_paths(tmp_path, monkeypatch, cache_dir_config):
    """Source paths in compiled programs are made relative to the checkout,
    so a checkout at another path hits the same cache entries."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compile_cache(tmp_path)
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    root = str(tmp_path.resolve())
    assert re.sub(pattern, "", f"{root}/src/repro/kernels/blocked.py") == "src/repro/kernels/blocked.py"
    assert re.sub(pattern, "", f"{root}-other/x.py") == f"{root}-other/x.py"
