"""Share of the traced window that is device self time under the
program's ``hvd_ssm_scan`` scope, forward, recomputation and backward,
worst chip: the state-space mixer's scan from ``(u, B, C, step)`` to
``o`` (the cumulative decay, the products inside a chunk, the chunk
states, the scan over the chunks, the inherited state's part, ``D * u``).
Left out when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_ssm_scan")
