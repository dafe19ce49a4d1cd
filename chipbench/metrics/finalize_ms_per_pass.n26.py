"""Host milliseconds per pass in ``finalize()`` and ``.labels`` for 67.1M
nodes (Graph500 SCALE 26; the benchmark's ``finalize`` span, layer:
finalize, ``cluster/api.py``)."""

from chipbench.metrics import finalize_ms_per_pass


def read(record):
    return finalize_ms_per_pass.read(record)
