"""Share of the chunked update's device time that the rule's bytes need at
the chip's peak HBM bandwidth (see ``_bytes_model``)."""

from chipbench.metrics import _update


def read(record):
    return _update.roofline(record, "chunked")
