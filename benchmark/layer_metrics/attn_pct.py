"""Share of the traced window that is device self time under the
program's ``hvd_attn`` scope, forward and backward, worst chip: everything
of a multi-head attention layer outside its kernel (the q, k and v
projections, rotary, the key/value heads' broadcast to the query heads and
the layout copies around the kernel, the output gate, the output
projection), of the full and of the sliding layers alike. Left out when
the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_attn")
