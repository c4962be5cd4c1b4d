"""Process start to ``hvd.init()`` returned and the chip gate passed:
imports, backend start, the mesh, the host services. Host clock."""

LAYER, UNIT, MOVES = "entry", "s", "setup_s"


def read(run):
    return run["init_s"]
