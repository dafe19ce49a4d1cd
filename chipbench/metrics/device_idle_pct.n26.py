"""Share of the traced window in which no operation ran on the device, at
Graph500 SCALE 26 (n = 2^26), where the host's 67.1M-label finalize leaves
the device idle (layer: device)."""

from chipbench.metrics import device_idle_pct


def read(record):
    return device_idle_pct.read(record)
