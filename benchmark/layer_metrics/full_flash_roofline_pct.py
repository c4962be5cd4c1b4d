"""The attention's share of its roofline in the layers without a window:
the least time the chip could take for what a step asks of it
(``families/<family>.kernel_work``'s ``full_flops`` and
``full_bytes``: the larger of required FLOPs over the bf16 peak and
required bytes over the HBM peak, from shapes alone, at the pairs the mask
leaves: half the square of the sequence with its diagonal) over
the device time a step spends under the program's ``hvd_attn_full``
scope, forward and backward summed: the same required work whatever
implements it. Left out when the family states no such work or the scope
is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "full_flops" not in work:
        return None
    found = scope_time.seconds_under(run, "hvd_attn_full")
    if found is None or not found[0]:
        return None
    least = max(work["full_flops"] / run["peaks"]["bf16_flops_per_s"],
                work["full_bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / found[0]
