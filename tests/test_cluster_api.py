"""Tests for the unified ``repro.cluster`` API: backend registry,
cross-backend equivalence, partial_fit resumability, checkpoint suspend /
resume, and config validation."""

import json

import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    ClusterState,
    StreamClusterer,
    available_backends,
    avg_f1,
    canonical_labels,
    cluster,
    get_backend,
    modularity,
)
from repro.graph.generators import sbm_stream

ALL_BACKENDS = (
    "chunked", "dense", "distributed", "multiparam", "oracle", "pallas", "scan",
)
SEQUENTIAL = ("oracle", "dense", "scan", "pallas")  # bit-exact, resumable
RESUMABLE = SEQUENTIAL + ("chunked",)


def _random_stream(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    e[:, 1] = np.where(e[:, 0] == e[:, 1], (e[:, 1] + 1) % n, e[:, 1])
    return e


def _cfg(backend, n=80, v_max=8, **kw):
    kw.setdefault("chunk", 64)
    return ClusterConfig(n=n, v_max=v_max, backend=backend, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_lists_all_seven_backends():
    assert available_backends() == ALL_BACKENDS


def test_backend_capabilities():
    """Resumable + out-of-core is the invariant: every registered tier
    threads a state pytree through partial_fit."""
    for name in ALL_BACKENDS:
        assert get_backend(name).resumable, name
    for name in SEQUENTIAL:
        assert get_backend(name).bit_exact, name
    assert not get_backend("chunked").bit_exact
    assert not get_backend("distributed").bit_exact
    assert get_backend("multiparam").bit_exact  # per sweep column
    # state-kind dispatch: the API layer no longer assumes ClusterState
    kinds = {name: get_backend(name).state_kind for name in ALL_BACKENDS}
    assert kinds["multiparam"] == "sweep"
    assert kinds["distributed"] == "sharded"
    assert all(kinds[b] == "cluster" for b in RESUMABLE)
    # labels of the wide-state tiers are derived at finalize time
    assert get_backend("multiparam").finalize_fn is not None
    assert get_backend("distributed").finalize_fn is not None
    with pytest.raises(KeyError):
        get_backend("nope")


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(n=0, v_max=4),
    dict(n=100, v_max=0),
    dict(n=100, v_max=None),
    dict(n=100, v_max=4, backend="does-not-exist"),
    dict(n=100, v_max=4, chunk=0),
    dict(n=100, backend="multiparam"),  # missing v_maxes
    dict(n=100, backend="multiparam", v_maxes=(4, 0)),
    dict(n=100, v_max=4, criterion="modularity"),  # not edge-free (paper §2.5)
    dict(n=100, v_max=4, n_shards=0),
])
def test_config_validation_rejects(bad):
    with pytest.raises(ValueError):
        ClusterConfig(**bad)


def test_config_json_roundtrip():
    cfg = ClusterConfig(n=50, backend="multiparam", v_maxes=(4, 8), chunk=32)
    assert ClusterConfig.from_json(cfg.to_json()) == cfg


def test_config_json_with_interpret_field_still_restores():
    # checkpoints written before interpret mode followed the platform
    cfg = ClusterConfig(n=50, v_max=8, backend="pallas", chunk=32)
    raw = json.loads(cfg.to_json())
    raw["interpret"] = True
    assert ClusterConfig.from_json(json.dumps(raw)) == cfg


# ---------------------------------------------------------------------------
# Cross-backend equivalence (acceptance: oracle == dense == scan bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v_max", [1, 3, 10, 100])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sequential_backends_bitexact(v_max, seed):
    n, m = 60, 400
    edges = _random_stream(n, m, seed)
    results = {
        b: cluster(edges, _cfg(b, n=n, v_max=v_max)) for b in SEQUENTIAL
    }
    ref = results["dense"].labels
    for b in SEQUENTIAL:
        assert np.array_equal(results[b].labels, ref), b
        # edge-free metrics agree across label spaces
        assert results[b].entropy == pytest.approx(results["dense"].entropy)
        assert results[b].avg_density == pytest.approx(
            results["dense"].avg_density
        )


@pytest.mark.parametrize("backend", ["chunked", "distributed"])
def test_parallel_backends_quality_parity_on_sbm(backend):
    n = 2000
    edges, truth = sbm_stream(n, 100, avg_degree=12, p_intra=0.8, seed=1)
    v_max = 48
    seq = cluster(edges, ClusterConfig(n=n, v_max=v_max, backend="dense"))
    kw = dict(n_shards=4) if backend == "distributed" else {}
    par = cluster(
        edges, ClusterConfig(n=n, v_max=v_max, backend=backend, chunk=512, **kw)
    )
    q_seq = modularity(edges, seq.labels)
    q_par = modularity(edges, par.labels)
    assert abs(q_seq - q_par) < 0.08, (q_seq, q_par)
    f_seq = avg_f1(seq.labels, truth)
    f_par = avg_f1(par.labels, truth)
    assert f_par > 0.6 * f_seq, (f_seq, f_par)


def test_multiparam_backend_selected_state_matches_scan():
    n, m = 100, 600
    edges = _random_stream(n, m, 7)
    res = cluster(
        edges,
        ClusterConfig(n=n, backend="multiparam", v_maxes=(4, 16, 64)),
    )
    best_v = res.info["best_v_max"]
    direct = cluster(edges, ClusterConfig(n=n, v_max=best_v, backend="scan"))
    assert np.array_equal(res.labels, direct.labels)
    assert len(res.info["rows"]) == 3


# ---------------------------------------------------------------------------
# Incremental ingestion (acceptance: partial_fit == one-shot, sequential)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", SEQUENTIAL)
@pytest.mark.parametrize("n_batches", [3])
def test_partial_fit_matches_one_shot(backend, n_batches):
    n, m = 80, 500
    edges = _random_stream(n, m, 11)
    one_shot = cluster(edges, _cfg(backend, n=n))
    sc = StreamClusterer(_cfg(backend, n=n))
    for batch in np.array_split(edges, n_batches):
        assert sc.partial_fit(batch) is sc
    res = sc.finalize()
    assert np.array_equal(res.labels, one_shot.labels)
    assert sc.edges_seen == m
    assert int(np.asarray(res.state.d).sum()) == 2 * m
    assert int(np.asarray(res.state.v).sum()) == 2 * m


def test_partial_fit_chunked_deterministic_and_valid():
    """Chunked partial_fit: batch boundaries are chunk boundaries, so labels
    are batching-dependent — but deterministic and a valid partition."""
    n, m = 100, 700
    edges = _random_stream(n, m, 13)

    def run():
        sc = StreamClusterer(_cfg("chunked", n=n))
        for batch in np.array_split(edges, 4):
            sc.partial_fit(batch)
        return sc.finalize()

    a, b = run(), run()
    assert np.array_equal(a.labels, b.labels)
    assert int(np.asarray(a.state.d).sum()) == 2 * m
    assert int(np.asarray(a.state.v).sum()) == 2 * m


def test_multiparam_partial_fit_matches_one_shot():
    """The sweep is a partial_fit backend now: k batches through the wider
    SweepState produce labels bit-identical to the one-shot call."""
    n, m = 80, 500
    edges = _random_stream(n, m, 11)
    cfg = ClusterConfig(n=n, backend="multiparam", v_maxes=(4, 16, 64))
    one_shot = cluster(edges, cfg)
    sc = StreamClusterer(cfg)
    for batch in np.array_split(edges, 5):
        sc.partial_fit(batch)
    res = sc.finalize()
    assert np.array_equal(res.labels, one_shot.labels)
    assert res.info["best_v_max"] == one_shot.info["best_v_max"]
    assert sc.edges_seen == m
    # finalize does not consume the sweep: the clusterer still threads the
    # wide state while the result carries the selected ClusterState view
    assert sc.state.c.ndim == 2 and res.state.c.ndim == 1


def test_distributed_partial_fit_deals_batches_onto_shards():
    n, m = 100, 800
    edges = _random_stream(n, m, 41)
    cfg = ClusterConfig(
        n=n, v_max=8, backend="distributed", n_shards=4, chunk=32
    )
    sc = StreamClusterer(cfg)
    for batch in np.array_split(edges, 4):
        sc.partial_fit(batch)
    res = sc.finalize()
    assert int(sc.state.cursor) == 4
    # every shard ingested one batch
    assert (np.asarray(sc.state.d).sum(axis=1) > 0).all()
    assert sc.edges_seen == m
    # the merged state makes edge-free metrics available for this tier
    assert res.state is not None and res.entropy is not None
    assert int(np.asarray(res.state.d).sum()) == 2 * m


def test_sweep_state_rejects_mismatched_v_maxes():
    """A carried/restored sweep state must match config.v_maxes — resuming
    under different parameters would silently corrupt the sweep."""
    cfg = ClusterConfig(n=20, backend="multiparam", v_maxes=(2, 4))
    sc = StreamClusterer(cfg)
    sc.partial_fit(_random_stream(20, 50, 43))
    with pytest.raises(ValueError, match="v_maxes"):
        cluster(
            _random_stream(20, 10, 44),
            ClusterConfig(n=20, backend="multiparam", v_maxes=(2, 8)),
            state=sc.state,
        )


def test_sharded_state_rejects_mismatched_shard_count():
    cfg = ClusterConfig(n=20, v_max=4, backend="distributed", n_shards=2)
    sc = StreamClusterer(cfg)
    sc.partial_fit(_random_stream(20, 50, 45))
    with pytest.raises(ValueError, match="n_shards"):
        cluster(
            _random_stream(20, 10, 46),
            cfg.replace(n_shards=3),
            state=sc.state,
        )


def test_finalize_before_any_batch_is_all_singletons():
    sc = StreamClusterer(_cfg("dense", n=25))
    res = sc.finalize()
    assert res.community_stats["n_communities"] == 25
    assert sc.edges_seen == 0


# ---------------------------------------------------------------------------
# Suspend / resume across "sessions" (checkpoint.manager integration)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "scan", "oracle"])
def test_save_restore_resumes_exactly(tmp_path, backend):
    n, m = 60, 400
    edges = _random_stream(n, m, 17)
    one_shot = cluster(edges, _cfg(backend, n=n))

    batches = np.array_split(edges, 3)
    sc = StreamClusterer(_cfg(backend, n=n))
    sc.partial_fit(batches[0])
    sc.save(str(tmp_path))

    sc2 = StreamClusterer.restore(str(tmp_path))  # fresh "session"
    assert sc2.config == sc.config
    assert sc2.edges_seen == sc.edges_seen
    for batch in batches[1:]:
        sc2.partial_fit(batch)
    assert np.array_equal(sc2.finalize().labels, one_shot.labels)


def test_restore_with_config_override(tmp_path):
    edges = _random_stream(40, 200, 19)
    sc = StreamClusterer(_cfg("dense", n=40))
    sc.partial_fit(edges)
    sc.save(str(tmp_path))
    # dense state is the layout every dense-space backend shares — resume the
    # same run on the scan tier
    sc2 = StreamClusterer.restore(
        str(tmp_path), config=_cfg("scan", n=40)
    )
    assert sc2.edges_seen == 200
    assert np.array_equal(
        np.asarray(sc2.state.c), np.asarray(sc.state.c)
    )


# ---------------------------------------------------------------------------
# Clustering result object
# ---------------------------------------------------------------------------

def test_clustering_bundles_edge_free_metrics():
    n = 60
    edges, _ = sbm_stream(n, 6, avg_degree=8, p_intra=0.9, seed=3)
    res = cluster(edges, ClusterConfig(n=n, v_max=16, backend="dense"))
    assert res.entropy is not None and res.entropy >= 0.0
    assert res.avg_density is not None and res.avg_density >= 0.0
    stats = res.community_stats
    assert stats["n_communities"] == res.n_communities >= 1
    assert isinstance(res.labels, np.ndarray)
    assert res.labels.min() == 0
    # canonical: labels are comparable across backends without relabeling
    assert np.array_equal(res.labels, canonical_labels(res.labels))


def test_cluster_state_counts_edges_and_ignores_pad():
    edges = _random_stream(30, 100, 23)
    padded = np.concatenate([edges, np.full((37, 2), -1, np.int32)])
    res = cluster(padded, ClusterConfig(n=30, v_max=6, backend="scan"))
    assert int(res.state.edges_seen) == 100
    ref = cluster(edges, ClusterConfig(n=30, v_max=6, backend="scan"))
    assert np.array_equal(res.labels, ref.labels)


def test_restore_rejects_cross_label_space_override(tmp_path):
    """An oracle checkpoint read as dense state would silently mislabel."""
    sc = StreamClusterer(_cfg("oracle", n=40))
    sc.partial_fit(_random_stream(40, 100, 37))
    sc.save(str(tmp_path))
    with pytest.raises(ValueError, match="label space"):
        StreamClusterer.restore(str(tmp_path), config=_cfg("scan", n=40))
    # same-space override (dense family) is fine
    sc2 = StreamClusterer(_cfg("dense", n=40))
    sc2.partial_fit(_random_stream(40, 100, 37))
    sc2.save(str(tmp_path))
    assert StreamClusterer.restore(
        str(tmp_path), config=_cfg("pallas", n=40)
    ).edges_seen == 100


def test_restore_rejects_cross_state_kind_override(tmp_path):
    """A sweep checkpoint is a wider pytree — restoring it as a 3n-int
    backend (or vice versa) is rejected by the state-kind check."""
    sc = StreamClusterer(ClusterConfig(n=30, backend="multiparam", v_maxes=(4, 8)))
    sc.partial_fit(_random_stream(30, 100, 53))
    sc.save(str(tmp_path))
    with pytest.raises(ValueError, match="state kind"):
        StreamClusterer.restore(str(tmp_path), config=_cfg("scan", n=30))
    # same-kind restore round-trips the full sweep
    sc2 = StreamClusterer.restore(str(tmp_path))
    assert sc2.edges_seen == sc.edges_seen
    assert np.array_equal(np.asarray(sc2.state.c), np.asarray(sc.state.c))
    assert np.array_equal(np.asarray(sc2.state.v_maxes), [4, 8])


def test_carried_state_must_match_config_n(tmp_path):
    """A state restored/carried into a different node-id space is rejected
    (out-of-range ids would be silently dropped by device scatters)."""
    sc = StreamClusterer(_cfg("scan", n=40))
    sc.partial_fit(_random_stream(40, 100, 29))
    sc.save(str(tmp_path))
    with pytest.raises(ValueError, match="n="):
        StreamClusterer.restore(str(tmp_path), config=_cfg("scan", n=99))
    with pytest.raises(ValueError, match="n="):
        cluster(
            _random_stream(40, 10, 31), _cfg("dense", n=99), state=sc.state
        )


def test_state_init_shapes():
    s = ClusterState.init(17)
    assert s.n == 17
    assert s.d.shape == s.c.shape == s.v.shape == (17,)
    assert int(s.edges_seen) == 0
