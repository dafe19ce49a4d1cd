"""Device dispatches per million live edges (``stream_dispatches`` counter
of ``StreamClusterer``, layer: entry points, ``cluster/api.py``)."""


def read(record):
    live = record["live_edges"]
    if not live:
        return None
    return sum(p["dispatches"] for p in record["passes"]) / (live / 1e6)
