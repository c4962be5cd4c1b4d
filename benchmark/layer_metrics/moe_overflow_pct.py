"""Share of the traced window that is device self time under the
program's ``hvd_moe_overflow`` scope, worst chip: the expert share's
full-size branch, the one a step takes when the token-slots that fall on
the experts held pass the bound its expert-order buffers are sized to
(``horovod_tpu/models/experts.py``: ``held_rows``). 0.0 when every step of
the window stayed under the bound: the scope's instructions are in the
executable and none of them ran. Left out when the scope is not in the
executable (a tree from before the bound)."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_moe_overflow")
