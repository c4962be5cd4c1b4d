"""The program's ``hvd_import`` span: first to last line of
``horovod_tpu/__init__.py`` (jax, flax and optax with it when nothing
imported jax before: ``jax_was_imported`` on the ``setup_spans`` line).
Left out where the program keeps no set-up record."""

from benchmark.harness import setup_spans

LAYER, UNIT, MOVES = "entry", "s", "setup_s"


def read(run):
    return setup_spans.span_seconds(run, "hvd_import")
