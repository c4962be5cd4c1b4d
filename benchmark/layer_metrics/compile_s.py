"""Wall seconds of the ahead-of-time compile of the cell's step: a real
compile in a checkout's first run, a read of the persistent cache after
(the earlier ``compile`` line says which, by hits and misses)."""

LAYER, UNIT, MOVES = "step builders", "s", "setup_s"


def read(run):
    return run["compile_s"]
