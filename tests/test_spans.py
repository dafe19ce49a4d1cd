"""The program's spans (``repro.obs``) in a profiler trace of a streamed
``fit``: where each lands, how many there are, and that tracing leaves the
labels as they were."""

import glob

import numpy as np
import pytest
from jax.profiler import ProfileData, trace

from repro.cluster import ClusterConfig, Clustering, StreamClusterer
from repro.graph.sources import BinaryFileSource

N, M, BATCH, SLICE = 4096, 20_000, 2048, 3000


@pytest.fixture(scope="module")
def edge_file(tmp_path_factory):
    edges = np.random.default_rng(13).integers(0, N, size=(M, 2), dtype=np.int32)
    path = tmp_path_factory.mktemp("spans") / "edges.bin"
    BinaryFileSource.write(path, edges)
    return path


def _run(path, megabatch_k):
    cfg = ClusterConfig(
        n=N, backend="chunked", v_max=64, batch_edges=BATCH, prefetch=2,
        megabatch_k=megabatch_k,
    )
    sc = StreamClusterer(cfg)
    sc.fit(BinaryFileSource(path, rows_per_slice=SLICE))
    result = sc.finalize()
    labels = result.labels
    assert result.labels is labels  # computed once, then cached
    return sc, labels


def _host_lines(log_dir):
    """Per host line, its ``repro.*`` spans as ``(name, start, end)``."""
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events
                if e.name.startswith("repro.")
            ]
            if spans:
                lines.append(spans)
    return lines


def _inside(inner, outers):
    return any(s <= inner[1] and inner[2] <= e for _, s, e in outers)


@pytest.mark.parametrize("megabatch_k", [None, 2], ids=["batches", "megabatches"])
def test_fit_spans(edge_file, tmp_path, megabatch_k):
    with trace(str(tmp_path)):
        sc, labels = _run(edge_file, megabatch_k)
    lines = _host_lines(tmp_path)

    def named(name, spans):
        return [sp for sp in spans if sp[0] == name]

    def count(name):
        return sum(len(named(name, spans)) for spans in lines)

    (caller,) = [spans for spans in lines if named("repro.dispatch", spans)]
    assert count("repro.dispatch") == sc.stream_dispatches
    # one item per batch (or megabatch) through the prefetch queue, and one
    # last wait that finds the end of the stream
    items = sc.stream_megabatches if megabatch_k else sc.stream_batches
    assert sc.stream_batches == -(-M // BATCH)
    assert count("repro.pipeline.wait") == items + 1
    # the worker runs up to prefetch=2 pulls ahead, so one more pull past
    # the end may run before the queue is shut down
    assert items + 1 <= count("repro.pipeline.stage") <= items + 2
    assert len(named("repro.pipeline.wait", caller)) == items + 1
    # every read of the source's slices (and the one that finds its end),
    # on the prefetch thread inside a stage
    assert count("repro.source.read") == -(-M // SLICE) + 1
    for spans in lines:
        for read in named("repro.source.read", spans):
            assert spans is not caller
            assert _inside(read, named("repro.pipeline.stage", spans))
    for name in ("repro.fit", "repro.clusterer.init", "repro.finalize",
                 "repro.finalize.fetch", "repro.labels"):
        assert len(named(name, caller)) == count(name) == 1, name
    (fetch,) = named("repro.finalize.fetch", caller)
    assert _inside(fetch, named("repro.finalize", caller))
    assert count("repro.labels.sort") == 0  # node ids take the dense path
    for d in named("repro.dispatch", caller):
        assert _inside(d, named("repro.fit", caller))

    _, untraced = _run(edge_file, megabatch_k)
    np.testing.assert_array_equal(labels, untraced)


def test_sort_span_nests_in_labels(tmp_path):
    """Labels outside a dense range are sorted, inside ``repro.labels``."""
    result = Clustering(state=None, config=None, raw_labels=np.array([9, -1, 9, 4]))
    with trace(str(tmp_path)):
        labels = result.labels
    np.testing.assert_array_equal(labels, [0, 1, 0, 2])
    (line,) = [spans for spans in _host_lines(tmp_path)]
    (sort,) = [sp for sp in line if sp[0] == "repro.labels.sort"]
    assert _inside(sort, [sp for sp in line if sp[0] == "repro.labels"])
