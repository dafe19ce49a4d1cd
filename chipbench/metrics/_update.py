"""Shared arithmetic of the device-update readers: the update's device time
from the trace, per live edge dispatched in the traced window (the whole
window is traced, so every live edge the window read was dispatched in
it)."""

from __future__ import annotations

from chipbench.metrics import _bytes_model

# the update's events in the trace, by the name the program gives them
PATTERNS = {
    "chunked": ("programs", r"^jit_chunked_update$"),
    "pallas": ("programs", r"^jit_pallas_update$"),
}


def _device_s(record, kind: str):
    t = record.get("trace")
    if t is None:
        return None
    level, pattern = PATTERNS[kind]
    s = t.device_s(pattern, level)
    return s if s > 0 else None


def ns_per_edge(record, kind: str):
    s = _device_s(record, kind)
    if s is None or not record["live_edges"]:
        return None
    return 1e9 * s / record["live_edges"]


def roofline(record, kind: str):
    s = _device_s(record, kind)
    if s is None or not record["live_edges"]:
        return None
    return _bytes_model.roofline_pct(record["live_edges"], s, record["device_kind"])
