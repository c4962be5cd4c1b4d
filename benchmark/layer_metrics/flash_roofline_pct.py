"""The flash kernel's share of its roofline: the least time the chip could
take for what a step asks of the kernel (``families/<family>.kernel_work``:
the larger of required FLOPs over the bf16 peak and required bytes over
the HBM peak; at sequence 2048 a causal kernel does S/4 = 512 FLOPs a
byte against the v5e's 240, so the FLOPs bind, by about two to one)
over the kernel's device time a step, forward and backward summed."""

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(run):
    work = run["kernel_work"]
    kernel_s = run["summary"]["category_s"].get("pallas_kernel", 0.0)
    if work is None or not kernel_s:
        return None
    least = max(work["flops"] / run["peaks"]["bf16_flops_per_s"],
                work["bytes"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / kernel_s
