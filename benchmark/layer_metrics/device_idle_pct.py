"""Share of the traced window (first to last device operation) in which
no operation ran on the chip, worst chip. The driver works the same share
out of ``device.busy_s`` and ``device.window_s``."""

LAYER, UNIT, MOVES = "device", "%", "step_ms"


def read(run):
    return 100.0 * run["summary"]["idle_share"]
