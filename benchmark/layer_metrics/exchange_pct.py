"""Share of the traced window that is device self time under the
program's ``hvd_exchange`` scope, worst chip: packing the gradients into
``ops/fusion.py``'s buckets, the collective, unpacking, an average's
division. The whole price of the layer, where ``exposed_collective_pct``
is its collectives alone; not zero on one chip, where the buckets are
packed for nobody. Left out when the scopes are not in the executable."""

from benchmark.harness import phases

LAYER, UNIT, MOVES = "gradient exchange", "%", "step_ms"


def read(run):
    return phases.device_pct(run, "exchange")
