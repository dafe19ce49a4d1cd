"""Host milliseconds inside the source's slice iterator per million live
edges (the benchmark's ``source_read`` spans around the program's source,
layer: source read, ``graph/sources.py``)."""


def read(record):
    live = record["live_edges"]
    if not live:
        return None
    return 1e3 * sum(p["read_s"] for p in record["passes"]) / (live / 1e6)
