"""Share of the traced window that is device self time under the
program's ``hvd_kda`` scope, forward, recomputation and backward, worst
chip: everything of the delta-attention mixer outside its scan (the three
projections, their causal convolutions and ``silu``, the L2 norms, the
low-rank decay and its ``softplus``, beta, the gated head norm, the output
projection). Left out when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_kda")
