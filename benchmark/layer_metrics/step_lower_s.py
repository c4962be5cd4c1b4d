"""jax's top-level lowering spans of the step's program (jaxpr to MLIR
module; a kernel body traced while the step is lowered is lowering), every
build before the set-up record closed, summed. Left out where the program
keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "step builders", "s", "setup_s"


def read(run):
    return setup_spans.of_step(run, "lower_s")
