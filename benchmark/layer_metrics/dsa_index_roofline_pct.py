"""The price of choosing the keys against the floor of scoring them: the
least time the chip could take for the indexer's work
(``families/<family>.kernel_work``'s ``index_flops`` and ``index_bytes``:
its projections, the scores of every causal pair and the KL's backward
over the selected ones, from shapes alone; the larger of FLOPs over the
bf16 peak and bytes over the HBM peak) over the device time a step spends
under the program's ``hvd_dsa_index`` and ``hvd_dsa_topk`` scopes
together (the selection counts no FLOPs: its time is what this share
prices). Left out when the family states no such work or either scope is
not in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "index_flops" not in work:
        return None
    found = [scope_time.seconds_under(run, scope)
             for scope in ("hvd_dsa_index", "hvd_dsa_topk")]
    if None in found or not sum(f[0] for f in found):
        return None
    least = max(work["index_flops"] / run["peaks"]["bf16_flops_per_s"],
                work["index_bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / sum(f[0] for f in found)
