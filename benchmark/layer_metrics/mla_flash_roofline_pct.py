"""The flash kernel's share of its roofline at latent attention's two
head sizes: the least time the chip could take for what a step asks of
the kernel (``families/<family>.kernel_work``'s ``flops`` and ``bytes``:
the larger of required FLOPs over the bf16 peak and required bytes over
the HBM peak; at sequence 4096 the FLOPs bind) over the device time a
step spends in the calls whose ``op_name`` ends ``attn/pallas_call``,
forward and backward summed. ``flash_roofline_pct`` is the same share
where the flash kernel is the step's only Pallas kernel. Left out when
the family states no such work or no such call is in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"] or {}
    if "flops" not in work:
        return None
    found = scope_time.seconds_under(run, "attn/pallas_call")
    if found is None or not found[0]:
        return None
    least = max(work["flops"] / run["peaks"]["bf16_flops_per_s"],
                work["bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / found[0]
