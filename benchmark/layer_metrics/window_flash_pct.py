"""Share of the traced window that is device self time under the
program's ``hvd_attn_window`` scope, forward and backward, worst chip: the
attention itself of the sliding-window layers (the windowed flash
kernel's calls, or whatever ran in its place). Left out when the scope is
not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_attn_window")
