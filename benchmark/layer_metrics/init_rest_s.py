"""``hvd_init`` less ``hvd_init_backend``: configuration, XLA flags, the
compile cache's placement, the distributed join and the host services
(the goodput ledger, the flight recorder). Left out where the program
keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "entry", "s", "setup_s"


def read(run):
    return setup_spans.span_seconds(run, "hvd_init", less="hvd_init_backend")
