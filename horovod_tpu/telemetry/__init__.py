"""Unified telemetry plane: metrics registry, Prometheus endpoint,
cross-rank trace merge, on-demand XLA profiling.

The reference's observability is four disconnected views (coordinator
Timeline, stall-inspector warnings, autotuner CSV, user prints). Here
one rank-local registry (``registry.py``) is fed by every subsystem and
exposed three ways: the ``/metrics``+``/healthz``+``/profile`` HTTP
plane (``server.py``), compact snapshots on the elastic KV heartbeat
path (cluster view + straggler flagging in ``elastic/driver.py``), and
Chrome-trace counter events merged across ranks (``merge.py`` +
``utils/timeline.py``). docs/OBSERVABILITY.md is the catalogue.
"""

from horovod_tpu.telemetry import instruments  # noqa: F401
from horovod_tpu.telemetry import ledger  # noqa: F401
from horovod_tpu.telemetry import report  # noqa: F401
from horovod_tpu.telemetry import scopes  # noqa: F401
from horovod_tpu.telemetry import startup  # noqa: F401
from horovod_tpu.telemetry.instruments import (  # noqa: F401
    DataInstruments,
    StepInstruments,
    build_info_gauge,
    data_instruments,
    enabled,
    install_compile_listeners,
    record_bucket,
    record_collective,
)
from horovod_tpu.telemetry.ledger import TimeLedger, get_ledger  # noqa: F401
from horovod_tpu.telemetry.merge import load_events, merge_traces  # noqa: F401
from horovod_tpu.telemetry.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from horovod_tpu.telemetry.server import MetricsServer  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "MetricsServer", "StepInstruments", "DataInstruments",
    "data_instruments", "enabled", "build_info_gauge",
    "install_compile_listeners", "record_collective", "record_bucket",
    "load_events", "merge_traces", "instruments", "ledger", "report",
    "scopes", "startup",
    "TimeLedger", "get_ledger",
]
