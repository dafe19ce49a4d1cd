"""Device dispatches per million live edges at Graph500 SCALE 26 (n = 2^26;
``stream_dispatches`` counter of ``StreamClusterer``, layer: entry points,
``cluster/api.py``)."""

from chipbench.metrics import dispatches_per_medge


def read(record):
    return dispatches_per_medge.read(record)
