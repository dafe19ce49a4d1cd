"""The benchmark's plain references equal the program's ``dense`` and
``chunked`` tiers at a tiny size (on the CPU)."""

import numpy as np
import pytest

from chipbench import graphgen, reference


def _stream(seed):
    """2,048 nodes, 16,384 edges, with PAD rows and self-loops planted."""
    e = graphgen.kronecker_edges(11, 8, (0.57, 0.19, 0.19, 0.05), seed)
    e[::97] = -1  # PAD rows
    e[5::101, 1] = e[5::101, 0]  # self-loops
    return e


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3])
@pytest.mark.parametrize("v_max", [8, 64])
@pytest.mark.parametrize("impl", ["sequential", "sequential_py"])
def test_sequential_equals_dense(seed, v_max, impl):
    from repro.cluster import ClusterConfig, cluster

    e = _stream(seed)
    stops = [4096, e.shape[0]]
    ref = getattr(reference, impl)(e, 2048, v_max, stops)
    for stop in stops:
        got = cluster(e[:stop], ClusterConfig(n=2048, v_max=v_max, backend="dense"))
        assert np.array_equal(reference.canonical(ref[stop]), got.labels)
        assert int(got.state.edges_seen) == reference.live_count(e[:stop])


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3])
@pytest.mark.parametrize("chunk", [256, 1024])
@pytest.mark.parametrize("impl", ["jacobi", "jacobi_py"])
def test_jacobi_equals_chunked(seed, chunk, impl):
    from repro.cluster import ClusterConfig, cluster

    e = _stream(seed)
    stops = [4 * chunk, e.shape[0]]
    ref = getattr(reference, impl)(e, 2048, 16, chunk, stops)
    for stop in stops:
        cfg = ClusterConfig(
            n=2048, v_max=16, backend="chunked", chunk=chunk, batch_edges=2 * chunk
        )
        got = cluster(e[:stop], cfg)
        assert np.array_equal(reference.canonical(ref[stop]), got.labels)
        assert int(got.state.edges_seen) == reference.live_count(e[:stop])


def test_jacobi_differs_from_sequential():
    """The two rules are different results, so each reference can tell a
    tier that runs the other one."""
    e = _stream(7)
    m = e.shape[0]
    seq = reference.sequential(e, 2048, 64, [m])[m]
    jac = reference.jacobi(e, 2048, 64, 1024, [m])[m]
    assert not np.array_equal(reference.canonical(seq), reference.canonical(jac))


def test_stops_must_lie_in_the_stream():
    e = _stream(0)
    with pytest.raises(ValueError):
        reference.sequential(e, 2048, 64, [e.shape[0] + 1])
    with pytest.raises(ValueError):
        reference.sequential_py(e, 2048, 64, [e.shape[0] + 1])
    with pytest.raises(ValueError):
        reference.jacobi(e, 2048, 64, 1024, [e.shape[0] + 1])
    with pytest.raises(ValueError):
        reference.jacobi_py(e, 2048, 64, 1024, [e.shape[0] + 1])


@pytest.mark.parametrize("impl", ["jacobi", "jacobi_py"])
def test_jacobi_stop_inside_a_chunk_ends_the_stream_there(impl):
    e = _stream(4)
    jac = getattr(reference, impl)
    got = jac(e, 2048, 16, 1024, [1500, 4096])
    assert np.array_equal(got[1500], jac(e[:1500], 2048, 16, 1024, [1500])[1500])
    assert np.array_equal(got[4096], jac(e, 2048, 16, 1024, [4096])[4096])


def test_canonical_renumbers_by_first_appearance():
    assert reference.canonical(np.array([7, 7, 3, 9, 3])).tolist() == [0, 0, 1, 2, 1]


@pytest.mark.parametrize("rule", ["sequential", "jacobi"])
def test_without_a_compiler_the_rules_run_in_python(monkeypatch, rule):
    e = _stream(9)
    m = e.shape[0]
    args = (e, 2048, 32, 1024, [m]) if rule == "jacobi" else (e, 2048, 32, [m])
    reference._library.cache_clear()
    monkeypatch.setattr(reference.shutil, "which", lambda name: None)
    try:
        assert reference._library() is None
        got = getattr(reference, rule)(*args)[m]
    finally:
        monkeypatch.undo()
        reference._library.cache_clear()
    assert np.array_equal(got, getattr(reference, rule)(*args)[m])
