"""Bytes the update rule itself touches per live edge, and the roofline
share they give.

Derivation, from the rule (Algorithm 1, and its Jacobi form): each live
edge ``(i, j)`` is

* read once: ``i`` and ``j``, two int32 ids — 8 B;
* ``d[i] += 1``, ``d[j] += 1``: two int32 words read and written — 16 B;
* ``c[i]``, ``c[j]`` read — 8 B;
* ``v[c_i] += 1``, ``v[c_j] += 1``: two int32 words read and written —
  16 B.

That is 48 B per live edge.  A move re-touches the same two ``v`` words and
adds one 4-byte label write; it is left out, so the count does not depend
on how many edges move.  What an implementation adds on top (a sink slot,
per-chunk memsets, whole-state DMAs, padding rows) is not counted either:
the roofline reads the same work whatever implements it.

The rule does no arithmetic worth counting against a FLOP peak, so the
least time is the bytes over the chip's HBM bandwidth.
"""

from __future__ import annotations

import json
import os

BYTES_PER_LIVE_EDGE = 8 + 16 + 8 + 16

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind missing from the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def roofline_pct(live_edges: int, device_s: float, device_kind: str) -> float:
    """Least time for the counted bytes at peak bandwidth, over the time."""
    least_s = BYTES_PER_LIVE_EDGE * live_edges / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
