"""Ahead-of-time v5e compiles of the device paths at real widths.

Nothing runs: each test lowers a jitted entry point for one chip of a
described ``v5e:2x2`` topology and asks the TPU compiler to accept it.
That catches what interpret mode cannot (tiling rules, scalar access to
VMEM, the VMEM budget).  The one kernel Mosaic still refuses, the
wavefront kernel, is pinned to its refusal, so a fix (or a new failure
mode) shows up here.  The persistent compile cache is off around these
compiles: such a cache entry could not be read back without a chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.chunked import chunked_update_megabatch
from repro.core.state import ClusterState, FleetState
from repro.graph.pipeline import DESC_COLS
from repro.kernels.edge_decide.ops import edge_decide
from repro.kernels.edge_stream.kernel import MAX_NODES
from repro.kernels.edge_stream.ops import (
    pallas_decode_update_megabatch,
    pallas_fleet_update,
    pallas_update,
    pallas_update_megabatch,
    pallas_wavefront_update,
)
from repro.kernels.seg_volume.ops import seg_volume

LIVEJOURNAL_N = 3_997_962
EXACT_N = 1 << 20
V_MAX = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(sharding, n):
    leaf = _sds(sharding, (n,))
    return ClusterState(d=leaf, c=leaf, v=leaf, edges_seen=_sds(sharding, ()))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_chunked_megabatch_compiles_at_livejournal_n(one_chip):
    compiled = chunked_update_megabatch.lower(
        _state(one_chip, LIVEJOURNAL_N),
        _sds(one_chip, (8, 1 << 20, 2)),
        _sds(one_chip, ()),
        chunk=1024,
    ).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 12 * LIVEJOURNAL_N


@pytest.mark.parametrize("n", [EXACT_N, LIVEJOURNAL_N, MAX_NODES])
def test_megabatch_kernel_compiles(one_chip, n):
    compiled = pallas_update_megabatch.lower(
        _state(one_chip, n),
        _sds(one_chip, (4, 1 << 16, 2)),
        V_MAX,
        chunk=1024,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_per_batch_kernel_compiles(one_chip):
    compiled = pallas_update.lower(
        _state(one_chip, EXACT_N),
        _sds(one_chip, (1 << 16, 2)),
        V_MAX,
        chunk=1024,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_kernel_past_vmem_is_refused(one_chip):
    with pytest.raises(Exception, match="vmem"):
        pallas_update.lower(
            _state(one_chip, 11_300_000),
            _sds(one_chip, (1 << 16, 2)),
            V_MAX,
            interpret=False,
        ).compile()


def test_fleet_kernel_compiles(one_chip):
    T, n = 16, 65_536
    leaf = _sds(one_chip, (T, n))
    state = FleetState(d=leaf, c=leaf, v=leaf, edges_seen=_sds(one_chip, (T,)))
    compiled = pallas_fleet_update.lower(
        state, _sds(one_chip, (T, 4096, 2)), V_MAX, chunk=1024,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_wavefront_kernel_is_refused(one_chip):
    with pytest.raises(NotImplementedError, match="gather"):
        pallas_wavefront_update.lower(
            _state(one_chip, EXACT_N),
            _sds(one_chip, (256, 64, 2)),
            _sds(one_chip, (4096, 2)),
            _sds(one_chip, (2,)),
            V_MAX,
            chunk=1024,
            interpret=False,
        ).compile()


@pytest.mark.parametrize("n", [4096, EXACT_N])
def test_decode_update_kernel_compiles(one_chip, n):
    # a K * B = 8M-edge compressed megabatch in 1024-row DVE3 blocks: the
    # flattened descriptor table (8,195 rows) is the largest SMEM operand
    compiled = pallas_decode_update_megabatch.lower(
        _state(one_chip, n),
        _sds(one_chip, (8 << 23,), jnp.uint8),
        _sds(one_chip, (8195, DESC_COLS)),
        V_MAX,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_seg_volume_kernel_compiles(one_chip):
    compiled = seg_volume.lower(
        _sds(one_chip, (1 << 16,)),
        _sds(one_chip, (1 << 16,), jnp.float32),
        4096,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_edge_decide_kernel_compiles(one_chip):
    compiled = edge_decide.lower(
        *[_sds(one_chip, (1 << 16,))] * 5, V_MAX, interpret=False
    ).compile()
    _assert_kernel(compiled)
