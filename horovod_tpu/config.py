"""Environment-variable configuration knobs.

Mirrors the reference's env-knob surface (``horovod/common/common.h:61-88``,
parsed at ``horovod/common/operations.cc:387-484`` and
``horovod/common/utils/env_parser.cc``) with the same ``HOROVOD_*`` names so
users of the reference find the knobs they know. Launcher rank contract
mirrors ``horovod/run/gloo_run.py:210-236``.
"""

import dataclasses
import os


def _env_int(name, default):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return int(v)


def _env_float(name, default):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return float(v)


def _env_bool(name, default=False):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.lower() not in ("0", "false", "no", "off")


def _env_str(name, default=None):
    v = os.environ.get(name)
    return default if v in (None, "") else v


# Default tensor-fusion buffer size: 64 MB, matching the reference default
# (horovod/common/operations.cc:403).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
# Background-cycle time in ms (reference default 5 ms, operations.cc:407).
DEFAULT_CYCLE_TIME_MS = 5.0
# Response-cache capacity (reference default 1024, global_state.h:88).
DEFAULT_CACHE_CAPACITY = 1024
# Stall-warning threshold in seconds (reference 60 s, stall_inspector.h).
DEFAULT_STALL_WARNING_TIME = 60.0


@dataclasses.dataclass
class Config:
    """Snapshot of all HOROVOD_* knobs at ``init()`` time."""

    # --- process identity (set by the hvdrun launcher; gloo_run.py:210) ---
    rank: int = 0
    size: int = 1
    local_rank: int = 0
    local_size: int = 1
    cross_rank: int = 0
    cross_size: int = 1

    # --- control plane (reference: HOROVOD_GLOO_RENDEZVOUS_ADDR/PORT) ---
    controller_addr: str = None
    controller_port: int = 0
    rendezvous_addr: str = None
    rendezvous_port: int = 0

    # --- process mesh (hvdrun --spmd-procs; cluster/procmesh.py) ---
    # number of jax.distributed processes forming the one logical mesh
    # (0 = HOROVOD_SIZE when a coordinator address is set)
    spmd_procs: int = 0
    # virtual CPU devices this process contributes to the mesh (0 = the
    # backend default; CPU-only, stands in for a TPU host's local chips)
    spmd_local_devices: int = 0
    # cross-process collectives impl for XLA:CPU (default "gloo")
    cpu_collectives: str = None

    # --- data plane tuning ---
    fusion_threshold: int = DEFAULT_FUSION_THRESHOLD
    # Default collective wire format for DistributedOptimizer(
    # compression=None): one of None (uncompressed), "bf16"/"fp16",
    # "float16", "fp8_e4m3"/"fp8", "fp8_e5m2", "int8"
    # (ops/compression.by_name). The autotuner's wire axis installs its
    # winner here (docs/AUTOTUNE.md); an explicit compression= argument
    # always wins over the config value.
    wire_dtype: str = None
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    batch_d2d_memcopies: bool = True

    # --- XLA overlap scheduling (bucketed reduce-scatter pipeline) ---
    # Compiling the pipeline is only half the job: without the async-
    # collective + latency-hiding scheduler flags XLA serializes each
    # reduce-scatter behind the compute that precedes it and the overlap
    # never materializes on device.
    xla_async_collectives: bool = True
    xla_latency_hiding_scheduler: bool = True

    # --- observability ---
    timeline: str = None
    timeline_mark_cycles: bool = False
    log_level: str = "warning"
    log_hide_timestamp: bool = False
    # metrics endpoint (telemetry/server.py): None = disabled, 0 = bind
    # an ephemeral port. The launcher assigns base_port + local_rank per
    # rank (run/launcher.py). Loopback by default — the endpoints are
    # unauthenticated (security note in docs/OBSERVABILITY.md).
    metrics_port: int = None
    metrics_addr: str = "127.0.0.1"
    profile_dir: str = None
    # flight recorder (horovod_tpu/diag): None = auto — on for
    # multi-process jobs (where post-mortem forensics matter and a
    # launcher owns the dump dir), off for single-process library use
    # (no surprise signal handlers inside a host application).
    # HOROVOD_FLIGHTREC=0/1 forces; _CAPACITY bounds the ring;
    # _DIR is where flightrec.rank<r>.json dumps land (hvdrun plumbs
    # this to --output-dir or a run-scoped temp dir).
    flightrec: bool = None
    flightrec_capacity: int = 4096
    flightrec_dir: str = None

    @property
    def flightrec_enabled(self):
        return self.size > 1 if self.flightrec is None else self.flightrec

    # --- stall inspector (stall_inspector.h:30-70) ---
    stall_check_disable: bool = False
    stall_warning_time: float = DEFAULT_STALL_WARNING_TIME
    stall_shutdown_time: float = 0.0

    # --- autotune (parameter_manager.h) ---
    autotune: bool = False
    autotune_log: str = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8

    # --- adasum ---
    adasum_chunk_size: int = 1 << 26

    @classmethod
    def from_env(cls) -> "Config":
        return cls(
            rank=_env_int("HOROVOD_RANK", 0),
            size=_env_int("HOROVOD_SIZE", 1),
            local_rank=_env_int("HOROVOD_LOCAL_RANK", 0),
            local_size=_env_int("HOROVOD_LOCAL_SIZE", 1),
            cross_rank=_env_int("HOROVOD_CROSS_RANK", 0),
            cross_size=_env_int("HOROVOD_CROSS_SIZE", 1),
            controller_addr=_env_str("HOROVOD_CONTROLLER_ADDR"),
            controller_port=_env_int("HOROVOD_CONTROLLER_PORT", 0),
            rendezvous_addr=_env_str("HOROVOD_GLOO_RENDEZVOUS_ADDR"),
            rendezvous_port=_env_int("HOROVOD_GLOO_RENDEZVOUS_PORT", 0),
            spmd_procs=_env_int("HOROVOD_SPMD_PROCS", 0),
            spmd_local_devices=_env_int("HOROVOD_SPMD_LOCAL_DEVICES", 0),
            cpu_collectives=_env_str("HOROVOD_CPU_COLLECTIVES"),
            fusion_threshold=_env_int(
                "HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD),
            wire_dtype=_env_str("HOROVOD_WIRE_DTYPE"),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME",
                                     DEFAULT_CYCLE_TIME_MS),
            cache_capacity=_env_int("HOROVOD_CACHE_CAPACITY",
                                    DEFAULT_CACHE_CAPACITY),
            xla_async_collectives=_env_bool(
                "HOROVOD_XLA_ASYNC_COLLECTIVES", True),
            xla_latency_hiding_scheduler=_env_bool(
                "HOROVOD_XLA_LATENCY_HIDING_SCHEDULER", True),
            hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"),
            batch_d2d_memcopies=_env_bool("HOROVOD_BATCH_D2D_MEMCOPIES", True),
            timeline=_env_str("HOROVOD_TIMELINE"),
            timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES"),
            metrics_port=_env_int("HOROVOD_METRICS_PORT", None),
            metrics_addr=_env_str("HOROVOD_METRICS_ADDR", "127.0.0.1"),
            profile_dir=_env_str("HOROVOD_PROFILE_DIR"),
            flightrec=(None if _env_str("HOROVOD_FLIGHTREC") is None
                       else _env_bool("HOROVOD_FLIGHTREC")),
            flightrec_capacity=_env_int("HOROVOD_FLIGHTREC_CAPACITY", 4096),
            flightrec_dir=_env_str("HOROVOD_FLIGHTREC_DIR"),
            log_level=_env_str("HOROVOD_LOG_LEVEL", "warning"),
            log_hide_timestamp=_env_bool("HOROVOD_LOG_HIDE_TIME"),
            stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
            stall_warning_time=_env_float(
                "HOROVOD_STALL_CHECK_TIME_SECONDS", DEFAULT_STALL_WARNING_TIME),
            stall_shutdown_time=_env_float(
                "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0),
            autotune=_env_bool("HOROVOD_AUTOTUNE"),
            autotune_log=_env_str("HOROVOD_AUTOTUNE_LOG"),
            autotune_warmup_samples=_env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES",
                                             3),
            autotune_steps_per_sample=_env_int(
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10),
            autotune_bayes_opt_max_samples=_env_int(
                "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20),
            autotune_gaussian_process_noise=_env_float(
                "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8),
            adasum_chunk_size=_env_int("HOROVOD_ADASUM_CHUNK_SIZE", 1 << 26),
        )


def xla_overlap_flags(cfg):
    """The libtpu/XLA flags that let the compiler actually overlap the
    bucketed reduce-scatter pipeline with backward compute: async
    collectives (collectives become start/done pairs other work can slide
    between) and the latency-hiding scheduler (which does the sliding).
    Returned as ``--flag=value`` strings for ``LIBTPU_INIT_ARGS``."""
    flags = []
    if cfg.xla_latency_hiding_scheduler:
        flags.append("--xla_tpu_enable_latency_hiding_scheduler=true")
    if cfg.xla_async_collectives:
        flags += [
            "--xla_tpu_enable_async_collective_fusion=true",
            "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
            "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
            "--xla_tpu_overlap_compute_collective_tc=true",
        ]
    return flags


def apply_xla_flags(cfg, env=None):
    """Merge :func:`xla_overlap_flags` into ``LIBTPU_INIT_ARGS`` — must run
    before the first jax backend touch (``basics.init()`` does). libtpu
    reads the variable once at initialization; CPU/GPU builds never read
    it, so this is a no-op off TPU. Flags the user already set (matched by
    name) are left exactly as the user wrote them."""
    env = os.environ if env is None else env
    existing = env.get("LIBTPU_INIT_ARGS", "")
    have = {f.split("=", 1)[0] for f in existing.split()}
    add = [f for f in xla_overlap_flags(cfg)
           if f.split("=", 1)[0] not in have]
    if add:
        env["LIBTPU_INIT_ARGS"] = " ".join(
            ([existing] if existing else []) + add)
        if _tpu_backend_already_live():
            import logging
            logging.getLogger("horovod_tpu").warning(
                "hvd.init() ran AFTER the jax TPU backend was initialized "
                "(something touched jax.devices()/arrays first): libtpu "
                "already read LIBTPU_INIT_ARGS, so the async-collective/"
                "latency-hiding scheduler flags were NOT picked up and the "
                "overlapped gradient pipeline will not overlap. Call "
                "hvd.init() before any jax work, or export the flags "
                "yourself (docs/PERFORMANCE.md).")
    return add


def _tpu_backend_already_live():
    """True when a TPU backend is already initialized in this process —
    the point after which LIBTPU_INIT_ARGS edits are silently ignored.
    Probes only; never initializes a backend itself. jax 0.9.0 has no
    public spelling of the probe; if the internal moves this raises
    and the warning above is not lost in silence."""
    import jax
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    return jax.devices()[0].platform == "tpu"


# jax's persistent compile cache, when nothing outside placed it: one
# fixed git-ignored directory in the checkout. The path is part of the
# cache key's environment, so it never carries a pid, a time or a
# temporary name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def apply_compile_cache(env=None):
    """Place jax's persistent compile cache — runs beside
    :func:`apply_xla_flags`, before the first backend touch. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the program uses it and sets no
    other path; where it is not, :data:`DEFAULT_COMPILE_CACHE_DIR`. The
    variable is exported either way, so processes started from here
    (hvdrun workers, serve replicas) share the one cache. Returns the
    directory."""
    import jax
    env = os.environ if env is None else env
    path = env.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE_DIR
    env["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path
