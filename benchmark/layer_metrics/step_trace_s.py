"""jax's top-level trace spans of the step's program (function to
jaxpr), every build before the set-up record closed, summed. The step's
program is the one lowered under ``hvd_lower``. Left out where the
program keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "step builders", "s", "setup_s"


def read(run):
    return setup_spans.of_step(run, "trace_s")
