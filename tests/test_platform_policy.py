"""The platform policy for the one installation there is (ISSUE 21):
``"tpu"`` is the TPU, a backend error is an error, peaks come from a table
keyed by ``device_kind``, and the compile cache sits where
``JAX_COMPILATION_CACHE_DIR`` says or at one fixed path in the checkout."""

import sys
from pathlib import Path

import jax
import pytest

from paddle_tpu import compile_cache
from paddle_tpu.ops import _common

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def test_only_tpu_is_a_tpu():
    assert _common.is_tpu_platform("tpu")
    assert not _common.is_tpu_platform("cpu")
    assert not _common.is_tpu_platform("some-plugin")


def test_on_tpu_does_not_swallow_a_backend_error(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "devices", broken)
    _common.on_tpu.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="failed to initialise"):
            _common.use_pallas()
    finally:
        _common.on_tpu.cache_clear()


def test_peaks_come_from_the_table_or_not_at_all():
    import bench
    v5e = bench.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("TPU v9 imaginary")


@pytest.fixture
def cache_config():
    """Restore jax's cache directory: the suite must not start writing a
    persistent cache because one test configured it."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_dir_from_outside_is_left_to_jax(monkeypatch, cache_config,
                                               tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch,
                                                        cache_config,
                                                        tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for cwd in (tmp_path, REPO / "tests"):
        monkeypatch.chdir(cwd)
        paths.append(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == paths[-1]
    assert paths[0] == paths[1] == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored
