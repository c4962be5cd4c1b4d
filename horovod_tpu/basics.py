"""Process-level lifecycle and identity: init / shutdown / rank / size.

Rebuilds the surface of ``horovod/common/basics.py:22-213`` (ctypes calls
into ``horovod_init``/``horovod_rank``/... exported at
``horovod/common/operations.cc:641-778``) for TPU. Identity mapping:

* ``rank()``/``size()``         — this process among all launched processes.
  The ``hvdrun`` launcher starts one process per TPU chip (single-host) or
  per TPU VM (multi-host pods), mirroring one-process-per-GPU in the
  reference (``horovod/run/gloo_run.py:53-111`` slot allocation).
* ``local_rank()``/``local_size()``   — within this host.
* ``cross_rank()``/``cross_size()``   — across hosts/slices (DCN axis).
* ``num_devices()``             — total TPU chips in the mesh; inside a
  compiled step, the per-chip identity is ``mesh_rank()`` from
  ``horovod_tpu.ops.collective``.

Unlike the reference there is no background communication thread here: on
TPU the data plane is compiled into the step function by XLA, so ``init()``
only establishes identity, the mesh, and host-side services (controller
client, timeline, stall inspector).
"""

import atexit
import logging
import os
import threading

import jax

from horovod_tpu.config import Config
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.telemetry import scopes, startup

logger = logging.getLogger("horovod_tpu")

_lock = threading.Lock()


class _State:
    """Process-global state (TPU analogue of ``HorovodGlobalState``,
    ``horovod/common/global_state.h:42-122``, minus the background thread)."""

    def __init__(self):
        self.initialized = False
        self.config = None
        self.mesh = None
        self.controller = None  # host-side controller client (set when used)
        self.timeline = None
        self.stall_inspector = None
        self.metrics_server = None
        self.flight_recorder = None
        self.ledger = None  # goodput time ledger (telemetry/ledger.py)
        self.preempt_handler = None  # graceful eviction (elastic/preempt.py)
        self.joined = False


_state = _State()


def _configure_logging(cfg):
    level = getattr(logging, cfg.log_level.upper(), logging.WARNING)
    fmt = "[%(levelname)s rank " + str(cfg.rank) + "] %(message)s"
    if not cfg.log_hide_timestamp:
        fmt = "%(asctime)s " + fmt
    handler = logging.StreamHandler()
    handler.setFormatter(logging.Formatter(fmt))
    logger.handlers[:] = [handler]
    logger.setLevel(level)


def init(num_slices=None, devices=None):
    """Initialize horovod_tpu (idempotent, like ``InitializeHorovodOnce``,
    ``horovod/common/operations.cc:584``).

    Reads the launcher env contract (``HOROVOD_RANK/SIZE/...``), joins the
    multi-process JAX runtime when launched multi-process, and installs the
    global device mesh.
    """
    with _lock:
        if _state.initialized:
            return
        with startup.span(scopes.INIT):
            _init(num_slices, devices)
    atexit.register(shutdown)


def _init(num_slices, devices):
    """``init()``'s work, in the four parts the set-up record names."""
    with startup.span(scopes.INIT_CONFIG):
        cfg = Config.from_env()
        _configure_logging(cfg)

        # XLA overlap flags (async collectives + latency-hiding scheduler)
        # must be in the environment before the first backend touch, or
        # the bucketed reduce-scatter pipeline compiles but never overlaps
        from horovod_tpu import config as config_lib
        config_lib.apply_xla_flags(cfg)
        config_lib.apply_compile_cache()

    # Multi-process: join the distributed JAX runtime so jax.devices()
    # spans every chip in the job. The coordinator address is provided by
    # the hvdrun launcher (TPU analogue of the gloo rendezvous address,
    # gloo_context.cc:41-50). cluster.ensure_distributed is the one
    # sanctioned jax.distributed.initialize call site (HVD-DISTINIT)
    # and also arms the CPU gloo collectives + forced per-process
    # device count before the first backend touch.
    with startup.span(scopes.INIT_DISTRIBUTED):
        from horovod_tpu.cluster import procmesh
        multiproc = procmesh.ensure_distributed(cfg)

    with startup.span(scopes.INIT_BACKEND) as backend:
        if multiproc and jax.process_count() > 1 and devices is None and \
                num_slices in (None, jax.process_count()):
            # ONE logical mesh spanning every process: dcn outer axis =
            # the process tier (DCN), data minor axis = this host's ICI
            # tier (docs/SCALING.md).
            m = procmesh.build_process_mesh()
            procmesh.assert_process_contiguous(m)
        else:
            if num_slices is None:
                num_slices = cfg.cross_size if cfg.cross_size > 1 else 1
            m = mesh_lib.build_mesh(devices=devices, num_slices=num_slices)
        mesh_lib.set_mesh(m)
        backend["devices"] = int(m.devices.size)

    _state.config = cfg
    _state.mesh = m
    _state.initialized = True

    # Host-side services (timeline, stall inspector, controller client)
    # attach lazily; see horovod_tpu.runtime.
    with startup.span(scopes.INIT_SERVICES):
        from horovod_tpu.runtime import services
        services.start(_state)

    logger.info(
        "horovod_tpu initialized: rank=%d size=%d local=%d/%d cross=%d/%d "
        "mesh=%s devices=%d", cfg.rank, cfg.size, cfg.local_rank,
        cfg.local_size, cfg.cross_rank, cfg.cross_size,
        dict(zip(m.axis_names, m.devices.shape)), m.devices.size)


def shutdown():
    """Tear down host-side services (``horovod_shutdown``,
    ``operations.cc:687``)."""
    with _lock:
        if not _state.initialized:
            return
        from horovod_tpu.runtime import services
        services.stop(_state)
        # a later init() may see a different device set (tests rebuild
        # meshes; elastic re-inits after membership changes) — the eager
        # path must not reuse a proc mesh over departed devices
        from horovod_tpu.ops import collective
        collective.invalidate_proc_mesh()
        _state.initialized = False
        _state.mesh = None
        _state.config = None


def is_initialized():
    return _state.initialized


def _cfg():
    if not _state.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call horovod_tpu.init()")
    return _state.config


def rank():
    """Rank of this process among all launched processes."""
    return _cfg().rank


def size():
    """Number of launched processes."""
    return _cfg().size


def local_rank():
    return _cfg().local_rank


def local_size():
    return _cfg().local_size


def cross_rank():
    return _cfg().cross_rank


def cross_size():
    return _cfg().cross_size


def num_devices():
    """Total TPU chips in the global mesh (the data-parallel world size of
    the compiled data plane)."""
    if not _state.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call horovod_tpu.init()")
    return _state.mesh.devices.size


def mesh():
    """The global ``jax.sharding.Mesh`` installed by ``init()``."""
    if not _state.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call horovod_tpu.init()")
    return _state.mesh


def data_axes():
    """Axis names gradients are reduced over, e.g. ``('data',)`` or
    ``('dcn', 'data')``."""
    return mesh_lib.data_axis_names(mesh())


def mpi_threads_supported():
    """Parity shim for ``hvd.mpi_threads_supported()``
    (``horovod/common/basics.py``): there is no MPI on TPU VMs; the control
    plane is TCP. Always False."""
    return False


def mpi_built():
    """Parity probe (reference ``basics.py:162``): MPI-free by design —
    the control plane is TCP, the data plane XLA/ICI + host rings."""
    return False


def mpi_enabled():
    return False


_gloo_loadable = None  # caches only a positive probe (cannot un-load)


def gloo_built():
    """Parity probe (reference ``basics.py:181``): the role Gloo plays
    in the reference (TCP collectives without MPI) is filled by the
    built-in C++ core — True when the native library is present and
    loadable. Loadability only: a capability probe must never kick off
    the make-based build (that is ``_core.build()``'s job at init).
    A successful load is cached (repeated ``CDLL`` calls would pile up
    dlopen references); a negative answer is re-probed, since init may
    build the library later in the process."""
    global _gloo_loadable
    import ctypes
    import os

    from horovod_tpu import _core
    if _core._lib is not None or _gloo_loadable:
        return True
    if not os.path.exists(_core._LIB_PATH):
        return False
    try:
        ctypes.CDLL(_core._LIB_PATH)
        _gloo_loadable = True
        return True
    except OSError:
        return False


_nccl_preinit_warned = False  # warn once per process, not per probe


def nccl_built():
    """Parity probe (reference ``basics.py:189``): the "NCCL of TPU" is
    the XLA/ICI collective path. Returns an int like the reference
    (which returns the NCCL version code): 0 when no TPU backend is
    live, 1 otherwise — code that version-gates NCCL-specific features
    (``nccl_built() >= 21000``) correctly takes its non-NCCL path here,
    while plain truthiness probes see "built".

    Before ``hvd.init()`` this returns 0 WITHOUT touching
    ``jax.devices()``: a capability probe must not initialize the local
    JAX backend out from under a pending ``jax.distributed`` setup in a
    multi-process pod. Probe after ``init()`` for the real answer."""
    if not is_initialized():
        global _nccl_preinit_warned
        if not _nccl_preinit_warned:
            _nccl_preinit_warned = True
            logger.warning(
                "nccl_built() probed before hvd.init(): the TPU backend "
                "is not attached yet, so this reports 0 (not built). "
                "Probe again after init() for the real answer.")
        return 0
    try:
        return int(any(d.platform == "tpu" for d in jax.devices()))
    # hvd-lint: disable=HVD-EXCEPT -- device probe: backend errors mean no TPU, report 0
    except Exception:
        return 0


def ddl_built():
    return False


def ccl_built():
    return False
