"""Ahead-of-time v5e compiles of the device paths at real widths.

Nothing runs: each test lowers a jitted entry point for one chip of a
described ``v5e:2x2`` topology and asks the TPU compiler to accept it.
That catches what interpret mode cannot (tiling rules, scalar access to
VMEM, the VMEM budget).  The one kernel Mosaic still refuses, the
wavefront kernel, is pinned to its refusal, so a fix (or a new failure
mode) shows up here.  The persistent compile cache is off around these
compiles: such a cache entry could not be read back without a chip.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.chunked import chunked_update, chunked_update_megabatch
from repro.core.fleet import fleet_update_chunked
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
GRAPH500_N = 1 << 22  # the chunked benchmark cell: SCALE 22, 2^20-row batches
PAPER_SCALE_N = 1 << 26  # Graph500 SCALE 26: 805 MB of state, past VMEM
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


def _fills(hlo, elems):
    """Values broadcast into an int32 array of ``elems`` elements."""
    consts = dict(
        re.findall(r"%(\S+) = s32\[\](?:\{[^}]*\})? constant\((-?\d+)\)", hlo)
    )
    return [
        int(consts[op])
        for shape, op in re.findall(
            r"= s32\[([\d,]+)\]\{[^}]*\} broadcast\(%([^)\s]+)\)", hlo
        )
        if math.prod(int(x) for x in shape.split(",")) == elems
    ]


def test_chunked_update_fills_nothing_state_sized_and_keeps_state_in_vmem(one_chip):
    # the chunk's winners come from a compare over the chunk alone: no
    # (n + 1)-sized array is filled per chunk
    compiled = chunked_update.lower(
        _state(one_chip, GRAPH500_N),
        _sds(one_chip, (1 << 20, 2)),
        _sds(one_chip, ()),
        chunk=1024,
    ).compile()
    hlo = compiled.as_text()
    assert _fills(hlo, GRAPH500_N + 1) == []
    # and the state stays in VMEM (memory space 1) across the scan, which a
    # cond in the chunk step would undo
    assert f"s32[{GRAPH500_N + 1}]{{0:T(1024)S(1)}}" in hlo


def test_chunked_update_at_paper_scale_keeps_one_state_in_hbm(one_chip):
    # the state is past VMEM, so every scatter of the scan runs against HBM:
    # in place, with no state-sized fill or copy; the only temporaries are
    # the sink slot's concatenation of the three n + 1 arrays
    n = PAPER_SCALE_N
    compiled = chunked_update.lower(
        _state(one_chip, n),
        _sds(one_chip, (1 << 20, 2)),
        _sds(one_chip, ()),
        chunk=1024,
    ).compile()
    hlo = compiled.as_text()
    assert f"s32[{n + 1}]{{0:T(1024)S(1)}}" not in hlo
    assert _fills(hlo, n + 1) == []
    assert not re.search(rf"= s32\[{n + 1}\]\{{[^}}]*\}} copy\(", hlo)
    state_bytes = 3 * (n + 1) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * state_bytes


@pytest.fixture(scope="module")
def fleet_two(one_chip):
    T = 2
    leaf = _sds(one_chip, (T, GRAPH500_N))
    state = FleetState(d=leaf, c=leaf, v=leaf, edges_seen=_sds(one_chip, (T,)))
    return T, fleet_update_chunked.lower(
        state, _sds(one_chip, (T, 1 << 20, 2)), _sds(one_chip, ()), chunk=1024
    ).compile()


def test_fleet_chunked_has_no_winner_fill(fleet_two):
    # under vmap the scatters keep their own zero fills; the per-chunk
    # winner array (filled with the chunk size) is gone
    T, compiled = fleet_two
    assert 1024 not in _fills(compiled.as_text(), T * (GRAPH500_N + 1))


def test_fleet_chunked_move_loops_add_no_state_copies(fleet_two):
    # under vmap the move loops select over the whole (T, n + 1) state, but
    # in place: the temporaries stay at about five state-sized arrays, as
    # with the parent's chunk-wide moves and winner fill
    T, compiled = fleet_two
    state_bytes = T * (GRAPH500_N + 1) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * state_bytes


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
