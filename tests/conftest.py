"""Test harness config.

Multi-host-without-a-cluster parity (SURVEY.md §4.2 #3, the DummyTransport
translation): all tests run on CPU with 8 virtual XLA devices so mesh /
shard_map / DP / TP code paths execute real collectives deterministically,
no TPU pod needed.  Must be set before jax initializes its backends.
"""

import os

# Force CPU even when the environment presets JAX_PLATFORMS — tests need
# the deterministic 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import gc

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_memory_per_module():
    """Drop jit executables + buffers between test modules — the suite
    compiles hundreds of programs (gradchecks alone build ~120 nets in
    f64) and the accumulated cache otherwise OOMs the process before the
    last modules run.  The process-level step cache pins the nets its
    cached closures capture, so it is cleared alongside."""
    yield
    from deeplearning4j_tpu.train.step_cache import clear_step_cache
    clear_step_cache()
    gc.collect()
    jax.clear_caches()
