"""horovod_tpu — a TPU-native distributed data-parallel training framework.

A from-scratch rebuild of the capabilities of Horovod v0.18.2
(reference: ``/root/reference``, see ``SURVEY.md``) designed TPU-first:

* The **data plane** is compiled: gradient fusion, allreduce, allgather,
  broadcast, Adasum and hierarchical (ICI x DCN) reductions are expressed as
  XLA collectives over a ``jax.sharding.Mesh`` (reference equivalent:
  ``horovod/common/ops/nccl_operations.cc``, ``mpi_operations.cc``).
* The **control plane** is a host-side core (TCP controller + HTTP-style
  rendezvous, name-negotiated readiness, response cache, stall inspector,
  timeline, autotuner) mirroring ``horovod/common/{controller.cc,
  operations.cc}`` — but it never touches tensor bytes on TPU: negotiation
  decides *what* to run, XLA executes it.
* The **user contract** is Horovod's: ``init()``, ``rank()/size()``,
  ``DistributedOptimizer``, ``broadcast_variables``, Join, and an
  ``hvdrun``-style launcher (reference: ``horovod/run/run.py``).

Top-level namespace re-exports the JAX-first API (reference equivalent:
``horovod/tensorflow/__init__.py`` / ``horovod/torch/__init__.py``).
"""

import sys as _sys
import time as _time

# the set-up record opens here, before the first heavy import, on the
# clock its other spans use (telemetry/startup.py)
_IMPORT_STARTED = _time.time()
_JAX_WAS_IMPORTED = "jax" in _sys.modules

from horovod_tpu import compat  # noqa: E402,F401  (installs jax.shard_map shim)
from horovod_tpu.basics import (
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    num_devices,
    mesh,
    data_axes,
    ccl_built,
    ddl_built,
    gloo_built,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
)
from horovod_tpu.ops.collective import (
    Sum,
    Average,
    Adasum,
    Min,
    Max,
    allreduce,
    allgather,
    broadcast,
    reducescatter,
    alltoall,
    mesh_rank,
    mesh_size,
)
from horovod_tpu.ops.compression import Compression
from horovod_tpu.ops.fusion import (autotune_fusion_threshold,
                                    fused_allreduce)
from horovod_tpu.hvd_jax import (
    DistributedOptimizer,
    DistributedGradientTransform,
    HorovodOptimizer,
    distributed_grad,
    distributed_value_and_grad,
    broadcast_variables,
    broadcast_parameters,
    broadcast_optimizer_state,
    allreduce_metrics,
    join,
)
from horovod_tpu import checkpoint
from horovod_tpu import ckpt
from horovod_tpu import data
from horovod_tpu import elastic
from horovod_tpu import telemetry

__version__ = "0.1.0"

__all__ = [
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "num_devices", "mesh", "data_axes", "mpi_threads_supported",
    "mpi_built", "mpi_enabled", "gloo_built", "nccl_built",
    "ddl_built", "ccl_built",
    "Sum", "Average", "Adasum", "Min", "Max",
    "allreduce", "allgather", "broadcast", "reducescatter", "alltoall",
    "mesh_rank", "mesh_size",
    "Compression", "fused_allreduce", "autotune_fusion_threshold",
    "DistributedOptimizer", "DistributedGradientTransform",
    "HorovodOptimizer",
    "distributed_grad", "distributed_value_and_grad",
    "broadcast_variables", "broadcast_parameters",
    "broadcast_optimizer_state", "allreduce_metrics", "join",
    "checkpoint", "ckpt", "data", "elastic", "telemetry",
]

telemetry.startup.RECORD.add(
    telemetry.scopes.IMPORT, _IMPORT_STARTED, _time.time(),
    jax_was_imported=_JAX_WAS_IMPORTED)
