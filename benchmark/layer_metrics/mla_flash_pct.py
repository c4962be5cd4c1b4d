"""Share of the traced window that is device self time of the flash
kernel as latent attention calls it (q and k 192 wide, v and o 128),
forward and backward, worst chip: the Pallas calls whose ``op_name`` ends
``attn/pallas_call``. ``flash_kernel_pct`` reads every ``tpu_custom_call``
as the flash kernel, and this cell's step holds a second Pallas kernel
(the grouped products), so the cell reports its kernel here. Left out
when no such call is in the executable."""

from benchmark.harness import scope_time

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    return scope_time.pct(run, "attn/pallas_call")
