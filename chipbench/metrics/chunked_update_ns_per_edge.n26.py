"""Device nanoseconds per live edge of the chunked update with the state in
HBM (Graph500 SCALE 26, n = 2^26; layer: device update)."""

from chipbench.metrics import _update


def read(record):
    return _update.ns_per_edge(record, "chunked")
