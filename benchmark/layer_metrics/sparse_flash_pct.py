"""Share of the traced window that is device self time under the
program's ``hvd_attn_sparse`` scope, forward, recomputation and backward,
worst chip: the attention itself of the layers with an indexer, over each
query's selected keys (the sparse kernels' calls, or whatever ran in
their place). Left out when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_attn_sparse")
