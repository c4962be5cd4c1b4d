"""jax's backend spans of the step's program, every build before the
set-up record closed, summed: a compile on a checkout's first run, a read
of the persistent cache after (``cache`` on the ``setup_spans`` line says
which). Left out where the program keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "step builders", "s", "setup_s"


def read(run):
    return setup_spans.of_step(run, "xla_s")
