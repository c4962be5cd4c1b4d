"""The sparse attention's share of its roofline: the least time the chip
could take for what a step asks of it (``families/<family>.kernel_work``'s
``sparse_flops`` and ``sparse_bytes``: the larger of required FLOPs over
the bf16 peak and required bytes over the HBM peak, from shapes alone, at
the selected pairs: ``min(t + 1, topk)`` keys for query ``t``) over the
device time a step spends under the program's ``hvd_attn_sparse`` scope,
forward, recomputation and backward summed: the same required work
whatever implements it. Left out when the family states no such work or
the scope is not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "sparse_flops" not in work:
        return None
    found = scope_time.seconds_under(run, "hvd_attn_sparse")
    if found is None or not found[0]:
        return None
    least = max(work["sparse_flops"] / run["peaks"]["bf16_flops_per_s"],
                work["sparse_bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / found[0]
