"""Share of the traced window that is device self time of matrix
multiplications and convolutions, a fusion rooted in one counted whole
(its fused epilogue is the same device event)."""

LAYER, UNIT, MOVES = "model", "%", "step_ms"


def read(run):
    return 100.0 * run["summary"]["category_share"].get("matmul_conv", 0.0)
