"""The delta scan's share of its roofline: the least time the chip could
take for the delta rule a step requires
(``families/<family>.kernel_work``'s ``delta_flops`` and ``delta_bytes``:
the larger of FLOPs over the bf16 peak and bytes over the HBM peak, from
shapes alone at the configuration's chunk, forward and backward, nothing
recomputed; at 32 heads of 128 in chunks of 64 the bytes bind, by about
2.5 to 1) over the device time a step spends under the program's
``hvd_kda_scan`` scope, recomputation included: the same required work
whatever implements it, plain XLA today or a kernel later. Left out when
the family states no such work or the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "delta_flops" not in work:
        return None
    found = scope_time.seconds_under(run, "hvd_kda_scan")
    if found is None or not found[0]:
        return None
    least = max(work["delta_flops"] / run["peaks"]["bf16_flops_per_s"],
                work["delta_bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / found[0]
