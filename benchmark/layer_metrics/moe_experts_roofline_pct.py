"""The routed experts' share of their roofline: the least time the chip
could take for the grouped products a step requires
(``families/<family>.kernel_work``'s ``grouped_flops`` and
``grouped_bytes``: the larger of FLOPs over the bf16 peak and bytes over
the HBM peak, at the expected number of held token-slots, nothing
recomputed; at 768 slots an expert the FLOPs bind, by about four to
three) over the device time a step spends under the program's
``hvd_moe_experts`` scope, recomputation included: the same required work
whatever implements it. Left out when the family states no such work or
the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "grouped_flops" not in work:
        return None
    found = scope_time.seconds_under(run, "hvd_moe_experts")
    if found is None or not found[0]:
        return None
    least = max(work["grouped_flops"] / run["peaks"]["bf16_flops_per_s"],
                work["grouped_bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / found[0]
