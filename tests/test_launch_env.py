"""Launch environment, device peaks and the compile-cache location."""
import os
import warnings

import jax
import pytest

from repro.launch import analysis, compile_cache, env


@pytest.fixture
def environ(monkeypatch):
    """A private copy of the process environment for ``apply_env``."""
    fake = dict(os.environ)
    fake.pop("XLA_FLAGS", None)
    monkeypatch.setattr(os, "environ", fake)
    return fake


@pytest.mark.parametrize("pin, has_tpu_flag", [
    (None, False), ("cpu", False), ("tpu", True),
])
def test_tpu_flags_only_under_a_tpu_pin(environ, pin, has_tpu_flag):
    environ.pop("JAX_PLATFORMS", None)
    if pin is not None:
        environ["JAX_PLATFORMS"] = pin
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax is already imported here
        env.apply_env()
    assert ("--xla_step_marker_location" in environ["XLA_FLAGS"]) == has_tpu_flag


def test_host_fingerprint_takes_the_backend_from_jax():
    assert env.host_fingerprint().endswith(f"-{jax.default_backend()}")


def test_peaks_are_keyed_by_device_kind():
    v5e = analysis.peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks_for(jax.devices()[0].device_kind)


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(os.path.dirname(__file__)), ".jax_cache")
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
