"""Share of the traced window that is device self time under the
program's ``hvd_kda_scan`` scope, forward, recomputation and backward,
worst chip: the delta rule from ``(q, k, v, g, beta)`` to ``o`` (the
running log-decay, the decayed triangles of every chunk, the
unit-triangular inverse and its products, the scan over the chunks with
the state). Left out when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_kda_scan")
