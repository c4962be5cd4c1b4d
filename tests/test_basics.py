"""Lifecycle + identity tests (reference pattern: test/test_common.py and
the rank/size checks at the top of test/test_tensorflow.py)."""

import os

import pytest


def test_init_idempotent(hvd):
    hvd.init()
    hvd.init()
    assert hvd.is_initialized()


def test_single_process_identity(hvd):
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1


def test_mesh_shape(hvd, n_devices):
    assert hvd.num_devices() == n_devices
    assert hvd.mesh().axis_names == ("data",)
    assert hvd.data_axes() == ("data",)


def test_mesh_2d(hvd2d, n_devices):
    m = hvd2d.mesh()
    assert m.axis_names == ("dcn", "data")
    assert m.devices.shape == (2, n_devices // 2)
    assert hvd2d.data_axes() == ("dcn", "data")


def test_uninitialized_raises():
    import horovod_tpu as hvd
    hvd.shutdown()
    with pytest.raises(RuntimeError):
        hvd.rank()
    with pytest.raises(RuntimeError):
        hvd.mesh()


def test_env_contract(monkeypatch):
    import horovod_tpu as hvd
    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_RANK", "3")
    monkeypatch.setenv("HOROVOD_SIZE", "8")
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "1")
    monkeypatch.setenv("HOROVOD_LOCAL_SIZE", "4")
    monkeypatch.setenv("HOROVOD_CROSS_RANK", "1")
    monkeypatch.setenv("HOROVOD_CROSS_SIZE", "2")
    # No coordinator addr -> stays single-process JAX but identity comes
    # from the env contract (what the launcher guarantees).
    hvd.init()
    try:
        assert hvd.rank() == 3
        assert hvd.size() == 8
        assert hvd.local_rank() == 1
        assert hvd.local_size() == 4
        assert hvd.cross_rank() == 1
        assert hvd.cross_size() == 2
        # cross_size=2 -> hierarchical 2-D mesh
        assert hvd.mesh().axis_names == ("dcn", "data")
    finally:
        hvd.shutdown()


def test_config_knobs(monkeypatch):
    from horovod_tpu.config import Config
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1048576")
    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "3.5")
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", "30")
    cfg = Config.from_env()
    assert cfg.fusion_threshold == 1048576
    assert cfg.cycle_time_ms == 3.5
    assert cfg.hierarchical_allreduce is True
    assert cfg.stall_warning_time == 30.0


def test_compile_cache_placed_from_outside_or_in_the_checkout():
    """config.apply_compile_cache: an outside JAX_COMPILATION_CACHE_DIR
    wins and is the only path set; unset, the one fixed directory in
    the checkout — the same one tests/conftest.py names."""
    import jax

    from horovod_tpu import config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert config.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        env = {}
        assert config.apply_compile_cache(env) == \
            config.DEFAULT_COMPILE_CACHE_DIR
        assert env == {"JAX_COMPILATION_CACHE_DIR":
                       config.DEFAULT_COMPILE_CACHE_DIR}
        env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
        assert config.apply_compile_cache(env) == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == "/somewhere/else"
        assert env == {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_mpi_threads_supported(hvd):
    assert hvd.mpi_threads_supported() is False


def test_built_probes():
    """Reference *_built() capability probes (basics.py:162-189): the
    MPI-era backends report absent, the roles that exist here report
    by their actual availability."""
    import horovod_tpu as hvd
    assert hvd.mpi_built() is False
    assert hvd.mpi_enabled() is False
    assert hvd.ddl_built() is False
    assert hvd.ccl_built() is False
    assert hvd.gloo_built() is True      # native TCP core ships built-in
    # int like the reference's version-code contract: 0 = no live TPU
    assert hvd.nccl_built() in (0, 1)


def test_nccl_built_preinit_warns_once(caplog):
    """ADVICE round 5: probing nccl_built() before init() silently says
    "not built"; it must warn — exactly once — so pre-init callers know
    the 0 is about timing, not capability."""
    import logging

    import horovod_tpu as hvd
    from horovod_tpu import basics

    hvd.shutdown()
    basics._nccl_preinit_warned = False  # fresh process-lifetime flag
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        assert hvd.nccl_built() == 0
        assert hvd.nccl_built() == 0
    warnings = [r for r in caplog.records
                if "probed before" in r.getMessage()]
    assert len(warnings) == 1
