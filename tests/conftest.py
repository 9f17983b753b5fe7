"""Test harness: force an 8-device virtual CPU platform *before* jax import.

Multi-chip behavior (shard_map reducers, hierarchical meshes) is validated on
virtual devices exactly as SURVEY.md §4 prescribes for the rebuild; the chip
is reached through ``chip_smoke.py`` and ``CGX_TEST_TPU=1 pytest -m tpu``.
"""

import os

# Force, don't setdefault: an inherited JAX_PLATFORMS=tpu (the Dockerfile
# sets it) must not move the suite off the virtual 8-device CPU mesh.
# CGX_TEST_TPU=1 opts out (the `pytest -m tpu` hardware run — the cpu pin
# would otherwise make every tpu-marked test self-skip).
_ON_TPU = os.environ.get("CGX_TEST_TPU", "0") == "1"
if not _ON_TPU:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# jax may already have been imported by a pytest plugin (jaxtyping), which
# captured JAX_PLATFORMS before we overrode it — force the config explicitly.
if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


def pytest_runtest_setup(item):
    """Skip @pytest.mark.tpu tests on the CPU suite (they run on real
    hardware via `pytest -m tpu` with default platform env)."""
    if item.get_closest_marker("tpu") and jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU backend")


@pytest.fixture(autouse=True)
def _clean_cgx_env(monkeypatch):
    """Isolate CGX_* env mutations per test (the config layer re-reads env on
    every call, matching reference ResetParamsFromEnv semantics)."""
    for key in list(os.environ):
        if key.startswith("CGX_"):
            monkeypatch.delenv(key, raising=False)
    yield


@pytest.fixture(autouse=True)
def _clear_registry():
    import torch_cgx_tpu

    torch_cgx_tpu.clear_registry()
    yield
    torch_cgx_tpu.clear_registry()


def fuzz_operand(rng, n, kind):
    """Shared operand recipes for the cross-impl codec fuzz tests
    (test_codec_host / test_codec_pallas): normal data, extreme magnitudes
    with denormal-scale spikes, and constant runs with outliers."""
    import numpy as _np

    if kind == 0:
        return rng.standard_normal(n).astype(_np.float32)
    if kind == 1:
        x = (rng.standard_normal(n) * 1e30).astype(_np.float32)
        x[:: max(1, n // 7)] = 1e-38
        return x
    x = _np.full(n, -7.25, _np.float32)
    x[:: max(1, n // 5)] = 3.5
    return x
