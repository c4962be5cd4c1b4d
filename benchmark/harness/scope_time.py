"""Device time under one of the program's ``jax.named_scope`` names.

The readers of ``mla_pct``, ``moe_route_pct``, ``moe_experts_pct`` and
``moe_experts_roofline_pct`` need the ``op_name`` of every instruction of
the traced step, and ``jobs/train.py`` keeps the instruction table to
itself (``layer_inputs`` holds ``xplane.reduce``'s summary only;
``harness/rebuild.phases_of`` makes the table a second time and hands on
``phases.summarize``'s sums). PR 27 added these metrics and may edit no
file the benchmark had, so the first of these readers builds the cell's
step once more from the command line, as ``rebuild.observe`` does, and
keeps the table under ``run["instruction_table"]`` for the others: one
more trace of the step's Python and one more executable out of the compile
cache this run filled (same key, same instruction names as the events of
``run["summary"]``), in a ``--trace 1`` run of a cell that lists one of
these metrics and in no other. Nothing is run on the device.

This file goes with ``rebuild.py`` when a ``benchmark`` PR lets
``jobs/train.py`` put its table under ``layer_inputs``.
"""

import importlib
import re
import sys
import traceback

from benchmark.harness import hlo, rebuild


def _table_of(run):
    """``hlo.instruction_table`` of the cell's compiled step, or ``None``
    (reason on stderr) where it cannot be built again."""
    if "instruction_table" not in run:
        try:
            import horovod_tpu as hvd

            config, traffic, seed = rebuild.cell_of_command_line(
                sys.argv[1:])
            family = importlib.import_module(
                f"benchmark.families.{config['family']}")
            built = family.build(config, traffic, hvd.mesh(), seed)
            text = built.step.lower(
                built.init_state(), *built.batch()).compile().as_text()
            run["instruction_table"] = hlo.instruction_table(text)
        except Exception:  # the boundary: the run's own result stands
            traceback.print_exc()
            print("benchmark: the step could not be built again; the "
                  "scope metrics are left out", file=sys.stderr)
            run["instruction_table"] = None
    return run["instruction_table"]


def seconds_under(run, scope):
    """``(self seconds under scope, window seconds)`` of the chip that
    spent most under ``scope`` (forward, recomputation and backward: the
    name sits inside ``jvp(...)`` and ``transpose(...)`` alike), or
    ``None`` with the reason on stderr when no instruction of the
    executable carries the name: a scope the program does not have, or an
    executable from before it, must never read as a small number."""
    table = _table_of(run)
    if table is None:
        return None
    named = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    under = {name for name, info in table.items()
             if named.search(info["op_name"])}
    if not under:
        print(f"benchmark: no instruction of the compiled step is under "
              f"{scope!r}; its metrics are left out", file=sys.stderr)
        return None
    worst = (0.0, 0.0)
    for reduced in run["summary"]["chips"].values():
        seconds = sum(s for name, s in reduced["by_name"].items()
                      if name in under)
        if seconds >= worst[0]:
            worst = (seconds, reduced["window_s"])
    return worst


def pct(run, scope):
    """The share of the traced window that is device self time under
    ``scope``, worst chip, in percent, or ``None``."""
    found = seconds_under(run, scope)
    if found is None:
        return None
    seconds, window_s = found
    return 100.0 * seconds / window_s
