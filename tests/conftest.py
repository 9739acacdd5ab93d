"""Test harness configuration.

The reference tests its "distributed" code on a single machine by running the
real engine in Spark ``local[*]`` mode (SURVEY.md §4).  The TPU-native analog:
run the real JAX engine on a virtual 8-device CPU mesh —
``--xla_force_host_platform_device_count=8`` — so sharding/collective code
paths execute for real without TPU hardware.  These env vars must be set
before jax is imported anywhere, hence this top-of-conftest block.
"""

import atexit
import os
import shutil
import sys
import tempfile

# plain `pytest` inserts tests/, not the repo root, on sys.path — the
# `scripts` package (imported by test_golden_report / test_profile_script)
# lives at the root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent compile cache is placed from OUTSIDE the program
# (parallel/distributed.py): one directory per test session keeps a run
# independent of whatever earlier sessions compiled or recorded.
_SESSION_CACHE = tempfile.mkdtemp(prefix="sm_xla_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE
atexit.register(shutil.rmtree, _SESSION_CACHE, ignore_errors=True)

import jax

assert len(jax.devices()) >= 8, "tests expect the 8-device virtual CPU mesh"

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def offgrid_ds(tmp_path_factory):
    """A fixture whose geometry is deliberately OFF the lattice: 9 rows
    bucket to 10 (real zero-row padding is exercised), 11 columns stay
    exact, and the peak count sits under the 4096-slot floor (real
    resident padding is exercised too).  Shared by test_buckets.py and
    the export cases of test_jax_backend.py."""
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset

    out = tmp_path_factory.mktemp("dsb")
    path, truth = generate_synthetic_dataset(
        out, nrows=9, ncols=11, formulas=None, present_fraction=0.5,
        noise_peaks=12, seed=41,
    )
    return SpectralDataset.from_imzml(path), truth


@pytest.fixture
def isolated_compile_cache(tmp_path, monkeypatch):
    """A private persistent-cache directory for ONE test (empty cache, no
    warmup/bucket/prime manifests): what an operator does by starting the
    process with another ``JAX_COMPILATION_CACHE_DIR``.  JAX opens one
    cache per process, hence the explicit reset on the way in and out."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = tmp_path / "xla"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    jax.config.update("jax_compilation_cache_dir", str(cache))
    compilation_cache.reset_cache()
    yield cache
    jax.config.update("jax_compilation_cache_dir", _SESSION_CACHE)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _reset_config_singleton():
    """Isolate the SMConfig process-global between tests."""
    from sm_distributed_tpu.utils.config import SMConfig

    SMConfig._instance = None
    yield
    SMConfig._instance = None


@pytest.fixture(autouse=True)
def _reset_device_breaker():
    """Isolate the device circuit breaker process-global between tests: a
    test whose jax path raises must not open the breaker and silently
    degrade every LATER jax test to numpy scoring."""
    from sm_distributed_tpu.models import breaker

    breaker.reset_device_breaker()
    yield
    breaker.reset_device_breaker()


@pytest.fixture(autouse=True)
def _reset_fault_listener():
    """Isolate the device-fault listener seam (models/faults.py): a
    scheduler built by one test must not keep routing fault reports into
    its (long-gone) pool's health tracker during later tests."""
    from sm_distributed_tpu.models import faults

    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _reset_oom_registry():
    """Isolate the OOM safe-batch memory (models/oom.py): a learned batch
    from one test must not silently shrink every later search on the same
    fixture shape."""
    from sm_distributed_tpu.models import oom

    oom.reset()
    yield
    oom.reset()
