"""Model FLOP/s utilisation: the FLOPs the forward and backward passes
require per item (``families/<family>.required_flops_per_item``: shapes
only, nothing recomputed) times the items a second a chip completed in
the untraced window, over the chip's published bf16 peak."""

LAYER, UNIT, MOVES = "step builders", "%", "step_ms"


def read(run):
    return (100.0 * run["required_flops_per_item"]
            * run["items_per_s_per_chip"]
            / run["peaks"]["bf16_flops_per_s"])
