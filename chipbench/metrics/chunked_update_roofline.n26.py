"""Share of the chunked update's device time that the rule's bytes need at
the chip's peak HBM bandwidth (see ``_bytes_model``), with the state in HBM
(Graph500 SCALE 26, n = 2^26), where the counted bytes do go to HBM."""

from chipbench.metrics import _update


def read(record):
    return _update.roofline(record, "chunked")
