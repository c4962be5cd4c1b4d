"""Share of the traced window that is device self time of Pallas custom
calls (``tpu_custom_call``: on these paths the flash attention kernel,
forward and backward). 0 where the step holds none."""

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    return 100.0 * run["summary"]["category_share"].get("pallas_kernel", 0.0)
