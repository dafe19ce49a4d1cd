"""Tier-equivalence and behaviour tests for the paper's Algorithm 1."""

import contextlib

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import streaming
from repro.core.chunked import cluster_stream_chunked
from repro.core.metrics import avg_f1, modularity, nmi
from repro.core.streaming import (
    PAD,
    canonical_labels,
    cluster_stream_dense,
    cluster_stream_oracle,
    cluster_stream_scan,
)
from repro.graph.generators import chung_lu_stream, ring_of_cliques, sbm_stream
from repro.graph.pipeline import pad_to_chunks
from repro.graph.stream import shard_stream


def _random_stream(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    e[:, 1] = np.where(e[:, 0] == e[:, 1], (e[:, 1] + 1) % n, e[:, 1])
    return e


@pytest.mark.parametrize("v_max", [1, 3, 10, 100])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_matches_dict_oracle(v_max, seed):
    n, m = 60, 400
    edges = _random_stream(n, m, seed)
    c_dict = cluster_stream_oracle(edges, v_max)
    c_arr = np.array([c_dict.get(i, 0) for i in range(n)])
    c_dense, _, _ = cluster_stream_dense(edges, v_max, n)
    assert np.array_equal(canonical_labels(c_arr), canonical_labels(c_dense))


@pytest.mark.parametrize("v_max", [2, 8, 64])
def test_scan_bitexact_vs_dense(v_max):
    n, m = 80, 600
    edges = _random_stream(n, m, 3)
    c_d, d_d, v_d = cluster_stream_dense(edges, v_max, n)
    c_s, d_s, v_s = cluster_stream_scan(jnp.asarray(edges), v_max, n)
    assert np.array_equal(np.asarray(c_s), c_d.astype(np.int32))
    assert np.array_equal(np.asarray(d_s), d_d.astype(np.int32))
    assert np.array_equal(np.asarray(v_s), v_d.astype(np.int32))


def test_chunk1_bitexact_vs_scan():
    """chunk=1 chunked clustering degenerates to the sequential algorithm."""
    n, m = 50, 300
    edges = _random_stream(n, m, 4)
    c_d, d_d, v_d = cluster_stream_dense(edges, 8, n)
    c_c, d_c, v_c = cluster_stream_chunked(jnp.asarray(edges), 8, n, chunk=1)
    assert np.array_equal(np.asarray(c_c), c_d.astype(np.int32))
    assert np.array_equal(np.asarray(v_c), v_d.astype(np.int32))


def test_pad_edges_are_noops():
    n = 30
    edges = _random_stream(n, 100, 5)
    padded = np.concatenate(
        [edges, np.full((37, 2), PAD, dtype=np.int32)], axis=0
    )
    c1, d1, v1 = cluster_stream_dense(edges, 6, n)
    c2, d2, v2 = cluster_stream_dense(padded, 6, n)
    assert np.array_equal(c1, c2) and np.array_equal(v1, v2)
    c3, _, _ = cluster_stream_scan(jnp.asarray(padded), 6, n)
    assert np.array_equal(np.asarray(c3), c1.astype(np.int32))


def test_ring_of_cliques_recovered():
    edges, truth = ring_of_cliques(10, 6, seed=0)
    n = 60
    # v_max ~ half the final clique volume is the sweet spot (joins must
    # happen while communities are still below threshold).
    c, _, _ = cluster_stream_dense(edges, 16, n)
    f1 = avg_f1(canonical_labels(c), truth)
    assert f1 > 0.8
    assert modularity(edges, c) > 0.5


def test_chunked_quality_parity_on_sbm():
    n = 2000
    edges, truth = sbm_stream(n, 100, avg_degree=12, p_intra=0.8, seed=1)
    v_max = 48
    c_seq, _, _ = cluster_stream_dense(edges, v_max, n)
    c_chk, _, _ = cluster_stream_chunked(jnp.asarray(edges), v_max, n, chunk=512)
    q_seq = modularity(edges, c_seq)
    q_chk = modularity(edges, np.asarray(c_chk))
    assert abs(q_seq - q_chk) < 0.05
    f_seq = avg_f1(canonical_labels(c_seq), truth)
    f_chk = avg_f1(canonical_labels(np.asarray(c_chk)), truth)
    assert f_chk > 0.8 * f_seq


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis)
# ---------------------------------------------------------------------------

stream_strategy = st.integers(min_value=0, max_value=2**31 - 1)


@settings(max_examples=25, deadline=None)
@given(seed=stream_strategy, v_max=st.integers(min_value=1, max_value=200))
def test_invariants_hold_on_random_streams(seed, v_max):
    """Invariants of Algorithm 1 state, for any stream and any v_max:

    * sum of community volumes == sum of degrees == 2 * (#live edges)
    * volume of community k == sum of degrees of its members
    * every node's community label is a node id that belongs to the community
      chain (labels form a valid partition)
    """
    n, m = 40, 250
    edges = _random_stream(n, m, seed)
    c, d, v = cluster_stream_dense(edges, v_max, n)
    assert d.sum() == 2 * m
    assert v.sum() == d.sum()
    vol_check = np.zeros(n, dtype=np.int64)
    np.add.at(vol_check, c, d)
    assert np.array_equal(vol_check, v)
    # partition validity: labels are in range and every non-empty community id
    # has positive volume
    assert (c >= 0).all() and (c < n).all()
    used = np.unique(c[d > 0])
    assert (v[used] > 0).all()


@settings(max_examples=15, deadline=None)
@given(seed=stream_strategy)
def test_vmax1_keeps_volume_bounded_growth(seed):
    """With v_max=1 no join can fire after a community reaches volume 2:
    community sizes stay tiny (pairs at most)."""
    n, m = 30, 200
    edges = _random_stream(n, m, seed)
    c, d, v = cluster_stream_dense(edges, 1, n)
    sizes = np.bincount(c, minlength=n)
    assert sizes.max() <= 2


@settings(max_examples=10, deadline=None)
@given(seed=stream_strategy)
def test_monotone_vmax_reduces_fragmentation(seed):
    """Larger v_max can only produce <= as many communities (on average).

    Not a strict theorem — checked as a trend over one stream with a wide
    spread of v_max; guards against sign errors in the threshold logic."""
    n, m = 60, 500
    edges = _random_stream(n, m, seed)
    counts = []
    for vm in (1, 10, 10_000):
        c, d, _ = cluster_stream_dense(edges, vm, n)
        counts.append(len(np.unique(c[d > 0])))
    assert counts[0] >= counts[1] >= counts[2] - 2


def _canonical_labels_loop(c):
    """Reference implementation of canonical_labels (per-element loop)."""
    c = np.asarray(c)
    _, inv = np.unique(c, return_inverse=True)
    first = {}
    out = np.empty_like(inv)
    nxt = 0
    for idx, lab in enumerate(inv):
        if lab not in first:
            first[lab] = nxt
            nxt += 1
        out[idx] = first[lab]
    return out


@settings(max_examples=30, deadline=None)
@given(seed=stream_strategy, lo=st.integers(-10, 0), hi=st.integers(1, 500))
def test_canonical_labels_matches_loop_reference(seed, lo, hi):
    rng = np.random.default_rng(seed)
    x = rng.integers(lo, hi, size=rng.integers(1, 400))
    # and labels in 0..min(hi, size) - 1, which take the dense path
    dense = rng.integers(0, min(hi, x.size), size=x.size)
    for labels in (x, dense):
        got = canonical_labels(labels)
        want = _canonical_labels_loop(labels)
        assert np.array_equal(got, want)
        # canonical form: labels are 0..K-1, first appearances are increasing
        assert got.min() == 0 and got.max() == len(np.unique(labels)) - 1
        first_pos = [np.argmax(got == k) for k in range(got.max() + 1)]
        assert first_pos == sorted(first_pos)


def _spans_entered(monkeypatch):
    """Record the names of the spans ``repro.core.streaming`` enters."""
    names = []

    def span(name):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(streaming, "span", span)
    return names


_SPARSE = np.array([20, 3, 20, 17, 3, 0, 23, 20, 17, 9, 3, 1])


@pytest.mark.parametrize(
    "labels, sorts",
    [
        (np.arange(50), False),  # every node on its own label
        (np.full(50, 7), False),  # all one label
        (np.arange(50)[::-1], False),
        (np.array([0, 49, 49, 3, 0] + [49] * 45), False),  # uses n - 1
        (_SPARSE, False),  # 7 labels spread over 0..2n - 1
        (_SPARSE.astype(np.int32), False),
        (_SPARSE.astype(np.int64), False),
        (_SPARSE.astype(np.uint64), False),  # np.take refuses uint64 indices
        (np.array([0]), False),
        (np.array([5]), True),  # hi + 1 > 2n
        (np.array([], dtype=np.int64), True),
        (np.array([], dtype=np.int32), True),
        (np.array([0, 1, 2, 11, 2, 1]), False),  # hi + 1 = 2n
        (np.array([0, 1, 2, 12, 2, 1]), True),  # hi + 1 > 2n
        (np.array([3, -1, 3, 0]), True),  # a negative label
    ],
)
@pytest.mark.parametrize("block", [streaming._BLOCK, 3])
def test_canonical_labels_dense_path(labels, sorts, block, monkeypatch):
    monkeypatch.setattr(streaming, "_BLOCK", block)  # 3: many blocks, threads
    spans = _spans_entered(monkeypatch)
    got = canonical_labels(labels)
    want = _canonical_labels_loop(labels)
    assert np.array_equal(got, want)
    assert got.shape == labels.shape and got.dtype == np.intp
    assert spans == (["repro.labels.sort"] if sorts else [])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_canonical_labels_many_blocks(seed, monkeypatch):
    """Hundreds of blocks on the threads, a few labels shared across all of
    them: the same labels as the loop, and no sort."""
    monkeypatch.setattr(streaming, "_BLOCK", 97)
    spans = _spans_entered(monkeypatch)
    rng = np.random.default_rng(seed)
    n = 20_000
    labels = np.arange(n, dtype=np.int32)
    moved = rng.random(n) < 0.5
    labels[moved] = rng.integers(0, 50, moved.sum()) * 397  # founders
    assert np.array_equal(canonical_labels(labels), _canonical_labels_loop(labels))
    assert spans == []


@pytest.mark.parametrize("block", [streaming._BLOCK, 2])
def test_canonical_labels_wrong_first_position_takes_the_sort_path(
    block, monkeypatch
):
    monkeypatch.setattr(streaming, "_BLOCK", block)
    idx = np.array([4, 2, 4, 0, 2, 4], dtype=np.intp)
    first = streaming._first_positions(idx, 5)
    assert list(first[[4, 2, 0]]) == [0, 1, 3]
    assert np.array_equal(
        streaming._first_appearance_ranks(idx, first), _canonical_labels_loop(idx)
    )
    later = first.copy()
    later[4] = 5  # a later occurrence of label 4
    assert streaming._first_appearance_ranks(idx, later) is None
    # canonical_labels handed such an array sorts, and is still right
    monkeypatch.setattr(streaming, "_first_positions", lambda i, k: later)
    spans = _spans_entered(monkeypatch)
    got = canonical_labels(idx)
    assert np.array_equal(got, _canonical_labels_loop(idx))
    assert spans == ["repro.labels.sort"]


def test_canonical_labels_examples():
    assert np.array_equal(canonical_labels([7, 7, 3, 7, 3, 9]), [0, 0, 1, 0, 1, 2])
    assert np.array_equal(canonical_labels([2]), [0])


def test_shard_stream_partitions_preserve_edges():
    edges = _random_stream(100, 777, 9)
    shards = shard_stream(edges, 8)
    flat = shards.reshape(-1, 2)
    live = flat[:, 0] != PAD
    assert live.sum() == 777
    assert np.array_equal(flat[live][: len(edges)], edges)


def test_pad_to_chunks_shapes():
    edges = _random_stream(50, 130, 2)
    chunks = pad_to_chunks(edges, 64)
    assert chunks.shape == (3, 64, 2)
    assert (chunks.reshape(-1, 2)[130:] == PAD).all()
