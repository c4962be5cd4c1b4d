"""Test harness: 8 virtual CPU devices standing in for an 8-chip TPU slice.

Reference test strategy (SURVEY.md §4): everything end-to-end through the
Python API with small world sizes. Here the "world" is a virtual 8-device
mesh (``--xla_force_host_platform_device_count=8``), matching how the driver
dry-runs the multi-chip path. Multi-process controller/launcher tests spawn
real localhost processes and don't need devices at all.
"""

import os

# Must happen before jax is imported anywhere.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache, shared BY INHERITANCE with every
# subprocess the suite spawns (elastic workers, hvdrun example runs, the
# dryrun's virtual-mesh subprocess): those re-compile the same small
# models over and over, and with the whole suite actually exercising the
# compiled data plane the repeated compiles dominate suite wall-time.
# Placed by the rule of config.apply_compile_cache: an outside setting
# wins, else the one fixed git-ignored directory in the checkout
# (tests/test_basics.py pins the two spellings together).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def n_devices():
    import jax
    return len(jax.devices())


@pytest.fixture()
def hvd():
    """An initialized horovod_tpu with a fresh 1-D mesh."""
    import horovod_tpu as hvd_mod
    hvd_mod.shutdown()
    hvd_mod.init()
    yield hvd_mod
    hvd_mod.shutdown()


@pytest.fixture()
def hvd2d():
    """An initialized horovod_tpu with a 2-D (dcn=2, data=4) mesh."""
    import horovod_tpu as hvd_mod
    hvd_mod.shutdown()
    hvd_mod.init(num_slices=2)
    yield hvd_mod
    hvd_mod.shutdown()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)
