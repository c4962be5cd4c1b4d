"""Share of the traced window in which a collective over the chips was
in flight (a synchronous one's span, or ``-start`` to ``-done``) and no
computation ran on that chip, worst chip: what an exchange optimisation
can win at most."""

LAYER, UNIT, MOVES = "gradient exchange", "%", "tokens_per_s_per_chip"


def read(run):
    if run["chips"] == 1:
        return None
    return 100.0 * run["summary"]["exposed_collective_share"]
