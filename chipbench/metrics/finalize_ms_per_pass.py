"""Host milliseconds per pass in ``finalize()`` and ``.labels`` (the
benchmark's ``finalize`` span, layer: finalize, ``cluster/api.py``)."""


def read(record):
    passes = record["passes"]
    if not passes:
        return None
    return 1e3 * sum(p["finalize_s"] for p in passes) / len(passes)
