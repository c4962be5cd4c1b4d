"""Share of the traced window that is device self time under the
program's ``hvd_ssm`` scope, forward, recomputation and backward, worst
chip: everything of the state-space mixer outside its scan (``in_proj``,
the causal convolution and its ``silu``, ``softplus``, the gate and the
group norm, ``out_proj``). Left out when the scope is not in the
executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_ssm")
