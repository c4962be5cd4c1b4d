"""The chip gate and the table of published peaks.

A cell is a statement about a TPU. The gate refuses every other platform,
every ``device_kind`` that ``peaks.json`` does not list and every machine
with fewer chips than the cell asks for, before anything is measured. It
has no option: a rehearsal off the chip patches ``REQUIRED_PLATFORM`` and
``PEAKS_FILE`` from its own scratch script.
"""

import json
import os
import sys

REQUIRED_PLATFORM = "tpu"
PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def load_peaks(device_kind):
    """The published peaks of ``device_kind``, or ``None``."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if isinstance(row, dict) and "alias_of" in row:
        row = table.get(row["alias_of"])
    return row if isinstance(row, dict) else None


def require_chips(chips):
    """``(devices, peaks)`` for a cell that asks for ``chips`` chips, or
    exit non-zero naming what jax found. Call after ``hvd.init()``: the
    first backend touch has to happen under the flags it sets."""
    import jax

    devices = jax.devices()
    found = (f"platform {devices[0].platform!r}, {len(devices)} x "
             f"{devices[0].device_kind!r}")
    if devices[0].platform != REQUIRED_PLATFORM:
        sys.exit(f"benchmark: a cell measures a {REQUIRED_PLATFORM.upper()} "
                 f"and jax found {found}; nothing was measured")
    peaks = load_peaks(devices[0].device_kind)
    if peaks is None:
        sys.exit(f"benchmark: device_kind {devices[0].device_kind!r} is not "
                 f"in {os.path.relpath(PEAKS_FILE)} ({found}); add its "
                 "published peaks with their source before measuring on it")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips and jax "
                 f"found {found}; nothing was measured")
    return devices, peaks
