"""Device selection, the peaks table and the compile-cache placement."""
from __future__ import annotations

import jax
import pytest

from repro.kernels import autotune, ops, peaks
from repro.launch import compile_cache


def test_interpret_only_on_cpu(monkeypatch):
    assert ops._default_interpret() is True          # tests run on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._default_interpret()


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True),
                                     ("TPU v4", False), ("cpu", False)])
def test_peaks_table_is_keyed_by_device_kind(kind, ok):
    if ok:
        assert peaks.device_peaks(kind).hbm_bytes_s == 819e9
    else:
        with pytest.raises(ValueError, match="no published peak rates"):
            peaks.device_peaks(kind)


def test_cpu_tunes_for_the_named_target():
    assert peaks.device_peaks() == peaks.PEAKS[peaks.TUNING_TARGET]


def test_autotune_fails_on_an_unknown_accelerator(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    import numpy as np
    with pytest.raises(ValueError, match="TPU v99"):
        autotune._shard_cost(np.full(256, 4), 128, 128, 128)


@pytest.fixture
def restore_cache_dir():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, restore_cache_dir,
                                 from_env):
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == compile_cache.DEFAULT_DIR
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
