"""Share of the traced window in which no operation ran on the device
(1 minus the union of the device's op intervals over the window)."""


def read(record):
    t = record.get("trace")
    if t is None or not t.n_devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
