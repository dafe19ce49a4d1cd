"""The benchmark's stream generator and file writer."""

import json
import os

import numpy as np
import pytest

from chipbench import graphgen
from chipbench.tests.tiny import ROOT

SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits
INITIATOR = (0.57, 0.19, 0.19, 0.05)


def test_deterministic_by_seed():
    a = graphgen.kronecker_edges(10, 16, INITIATOR, SEED)
    b = graphgen.kronecker_edges(10, 16, INITIATOR, SEED)
    c = graphgen.kronecker_edges(10, 16, INITIATOR, SEED + 1)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_seed_bits_past_32_change_the_stream():
    a = graphgen.kronecker_edges(8, 16, INITIATOR, 7)
    b = graphgen.kronecker_edges(8, 16, INITIATOR, 2**40 + 7)
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("scale,edgefactor", [(10, 16), (12, 4)])
def test_shape_ids_and_degree_sum(scale, edgefactor):
    n, m = 1 << scale, edgefactor << scale
    e = graphgen.kronecker_edges(scale, edgefactor, INITIATOR, SEED)
    assert e.shape == (m, 2) and e.dtype == np.int32
    assert e.min() >= 0 and e.max() < n
    deg = np.bincount(e.ravel(), minlength=n)
    assert deg.size == n and int(deg.sum()) == 2 * m


def test_blocks_make_the_same_distribution(monkeypatch):
    """A stream made in several blocks has the initiator's skew: the share
    of endpoints whose top id bit (before the permutation) is 0 is A + B."""
    monkeypatch.setattr(graphgen, "BLOCK_ROWS", 1 << 12)
    scale, m = 12, 16 << 12
    monkeypatch.setattr(graphgen.jax.random, "permutation",
                        lambda key, n: graphgen.jnp.arange(n))
    e = graphgen.kronecker_edges(scale, 16, INITIATOR, SEED)
    assert e.shape == (m, 2)
    top = e >> (scale - 1)
    assert abs(np.mean(top[:, 0] == 0) - 0.76) < 0.01  # A + B
    assert abs(np.mean(top[:, 1] == 0) - 0.76) < 0.01  # A + C
    both = np.mean((top[:, 0] == 0) & (top[:, 1] == 0))
    assert abs(both - 0.57) < 0.01  # A


def test_configs_are_graph500_scale_22():
    for name in ("graph500-22-chunked", "graph500-22-pallas"):
        with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
            conf = json.load(f)
        assert (conf["scale"], conf["edgefactor"]) == (22, 16)
        assert conf["initiator"] == list(INITIATOR)
        assert (conf["n"], conf["m"]) == (1 << 22, 16 << 22)
        assert conf["reduced"] == []


def test_binary_file_holds_the_edges(tmp_path):
    from repro.graph.sources import BinaryFileSource

    e = graphgen.kronecker_edges(10, 16, INITIATOR, SEED)
    path = str(tmp_path / "e.bin")
    assert graphgen.write_binary(path, e) == e.nbytes
    assert np.array_equal(BinaryFileSource(path).materialize(), e)
