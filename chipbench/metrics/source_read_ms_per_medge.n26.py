"""Host milliseconds inside the source's slice iterator per million live
edges at Graph500 SCALE 26 (n = 2^26; the benchmark's ``source_read`` spans,
layer: source read, ``graph/sources.py``)."""

from chipbench.metrics import source_read_ms_per_medge


def read(record):
    return source_read_ms_per_medge.read(record)
