"""Device nanoseconds per live edge of the chunked update, summed from its
events in the trace (layer: device update)."""

from chipbench.metrics import _update


def read(record):
    return _update.ns_per_edge(record, "chunked")
