"""Share of the traced window that is device self time under the
program's ``hvd_moe_route`` scope, forward, recomputation and backward,
worst chip: the router product, sigmoid, top-k, the sort into expert
order, the gather of tokens into it and the weighted way back. Left out
when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_moe_route")
