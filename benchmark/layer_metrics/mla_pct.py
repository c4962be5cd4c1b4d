"""Share of the traced window that is device self time under the
program's ``hvd_mla`` scope, forward, recomputation and backward, worst
chip: everything of latent attention outside its kernel (the projections
to q and to the latent, the latent's norm, the expansion to k_nope and v,
rotary, building k from k_nope and the one shared k_pe, the output
projection). Left out when the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "hvd_mla")
