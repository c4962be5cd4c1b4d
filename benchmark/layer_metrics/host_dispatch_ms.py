"""Median of the program's ``hvd_step`` host spans under the profiler:
what one call of the step costs the host, from placing the state to the
executable's launch. Against ``step_ms`` it is the room left before the
host sets the pace; the ``hvd_place`` / ``hvd_launch`` medians inside it
are on the earlier ``phases`` line. Left out when the trace held no
``hvd_step`` span."""

from benchmark.harness import phases

LAYER, UNIT, MOVES = "step builders", "ms", "step_ms"


def read(run):
    found = phases.of_run(run)
    if found is None or not found["host"]["steps"]:
        return None
    return found["host"]["hvd_step_ms"]
