"""Megabytes (10^6) a chip's collectives carry over the ``data`` axis in
one step, read from the compiled step's HLO text. A one-chip cell has no
such axis and reports nothing; what its program's collectives compiled to
is on the earlier ``collectives`` line."""

LAYER, UNIT, MOVES = "gradient exchange", "MB", "tokens_per_s_per_chip"


def read(run):
    if run["chips"] == 1:
        return None
    return sum(slot["bytes"] for axes, slot in run["collectives"].items()
               if "data" in axes.split("+")) / 1e6
