"""The program's ``hvd_init_backend`` span: the first backend touch of
``hvd.init()`` (the mesh built over ``jax.devices()``). Left out where
the program keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "entry", "s", "setup_s"


def read(run):
    return setup_spans.span_seconds(run, "hvd_init_backend")
