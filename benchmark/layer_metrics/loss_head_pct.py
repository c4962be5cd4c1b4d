"""Share of the traced window that is device self time under the
program's ``hvd_loss`` scope or a ``lm_head`` module, forward and
backward, worst chip: the projection onto the vocabulary, the float32
log-softmax over it and the loss arithmetic. Left out when the scopes are
not in the executable."""

from benchmark.harness import phases

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return phases.device_pct(run, "loss_head")
