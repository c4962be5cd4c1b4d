"""Share of the traced window that is device self time under the
program's ``hvd_dsa_topk`` scope, worst chip: the selection of each
query's keys by the indexer's scores (the top-k of every causal key's
score, with the scores it ranks). Left out when the scope is not in the
executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_dsa_topk")
