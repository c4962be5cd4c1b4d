"""How many times the step's program was built before the set-up record
closed: its top-level lowerings, 1 if the first call reuses what
``hvd_lower`` made (a trace that jax's own cache answers leaves a span of
no length and is no build; ``traces`` on the ``setup_spans`` line counts
those too). Left out where the program keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "step builders", "count", "setup_s"


def read(run):
    return setup_spans.of_step(run, "builds")
