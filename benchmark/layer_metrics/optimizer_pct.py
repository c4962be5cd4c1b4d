"""Share of the traced window that is device self time under the
program's ``hvd_optimizer`` scope, worst chip: the inner optax
transform's update (AdamW, SGD momentum) and ``optax.apply_updates``.

A floor of what the optimizer costs, not the whole of it: a fusion is
booked under its own instruction's ``op_name``, and wherever nothing stands
between a gradient and its update the compiler fuses the update into the
backward fusion that makes the gradient (every weight of ResNet-101 on one
chip, PERF.md section 5), which is then booked as backward. An unpacking
slice of the exchange can be fused into an optimizer fusion the same way,
so the split with ``exchange_pct`` can lean. Left out when the scopes are
not in the executable."""

from benchmark.harness import phases

LAYER, UNIT, MOVES = "step builders", "%", "step_ms"


def read(run):
    return phases.device_pct(run, "optimizer")
