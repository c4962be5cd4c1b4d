"""Share of the traced window that is device self time under the
program's ``hvd_dsa_index`` scope, forward, recomputation and backward,
worst chip: the lightning indexer of the layers that have one (its
projections of the detached input, the LayerNorm on its key, rotary) and
the indexer's KL loss (the main attention's head-averaged probabilities
over each query's selected keys, the indexer's scores of them again, the
KL's terms and its gradient to the indexer). Left out when the scope is
not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_dsa_index")
