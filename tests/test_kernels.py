"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.edge_decide.ops import edge_decide
from repro.kernels.edge_decide.ref import edge_decide_ref
from repro.kernels.edge_stream.kernel import LANES, SUBLANES, state_rows
from repro.kernels.edge_stream.ops import edge_stream_cluster
from repro.kernels.edge_stream.ref import edge_stream_ref
from repro.kernels.seg_volume.ops import seg_volume
from repro.kernels.seg_volume.ref import seg_volume_ref


def _stream(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    e[:, 1] = np.where(e[:, 0] == e[:, 1], (e[:, 1] + 1) % n, e[:, 1])
    return e


# ---------------------------------------------------------------------------
# edge_stream: bit-exact sequential clustering, shape sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(16, 40), (100, 700), (513, 3000)])
@pytest.mark.parametrize("chunk", [1, 64, 500])
@pytest.mark.parametrize("v_max", [1, 16, 512])
def test_edge_stream_kernel_bitexact(n, m, chunk, v_max):
    e = jnp.asarray(_stream(n, m, n + m))
    c_k, d_k, v_k = edge_stream_cluster(e, v_max, n, chunk=chunk)
    c_r, d_r, v_r = edge_stream_ref(e, v_max, n)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))


def test_edge_stream_kernel_handles_pad_rows():
    n = 32
    e = _stream(n, 50, 0)
    padded = np.concatenate([e, np.full((30, 2), -1, np.int32)])
    c_k, _, _ = edge_stream_cluster(jnp.asarray(padded), 8, n, chunk=16)
    c_r, _, _ = edge_stream_ref(jnp.asarray(e), 8, n)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


# ---------------------------------------------------------------------------
# edge_decide: decision stage, shape/dtype sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [5, 128, 1000, 4096])
@pytest.mark.parametrize("v_max", [1, 100])
@pytest.mark.parametrize("block_rows", [8, 16])
def test_edge_decide_matches_ref(b, v_max, block_rows):
    rng = np.random.default_rng(b + v_max)
    vci = jnp.asarray(rng.integers(0, 200, b), jnp.int32)
    vcj = jnp.asarray(rng.integers(0, 200, b), jnp.int32)
    di = jnp.asarray(rng.integers(1, 50, b), jnp.int32)
    dj = jnp.asarray(rng.integers(1, 50, b), jnp.int32)
    live = jnp.asarray(rng.integers(0, 2, b), jnp.int32)
    a_k, m_k = edge_decide(vci, vcj, di, dj, live, v_max, block_rows=block_rows)
    a_r, m_r = edge_decide_ref(vci, vcj, di, dj, live, v_max)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))


# ---------------------------------------------------------------------------
# seg_volume: histogram-as-matmul, shape/dtype sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k", [(100, 17), (2048, 256), (5000, 1000)])
@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_seg_volume_matches_ref(b, k, wdtype):
    rng = np.random.default_rng(b * k)
    labels = jnp.asarray(rng.integers(0, k, b), jnp.int32)
    if wdtype == jnp.int32:
        w = jnp.asarray(rng.integers(0, 10, b), wdtype)
    else:
        w = jnp.asarray(rng.random(b), wdtype)
    out_k = seg_volume(labels, w, k)
    out_r = seg_volume_ref(labels, w, k)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("block_b,block_k", [(128, 128), (512, 512)])
def test_seg_volume_block_shape_sweep(block_b, block_k):
    rng = np.random.default_rng(7)
    b, k = 3000, 700
    labels = jnp.asarray(rng.integers(0, k, b), jnp.int32)
    w = jnp.asarray(rng.random(b), jnp.float32)
    out_k = seg_volume(labels, w, k, block_b=block_b, block_k=block_k)
    out_r = seg_volume_ref(labels, w, k)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("n", [1, 1023, 1024, 1025, 3_997_962])
def test_state_rows_cover_n_in_whole_tiles(n):
    rows = state_rows(n)
    assert rows % SUBLANES == 0
    assert rows * LANES >= n > (rows - SUBLANES) * LANES
