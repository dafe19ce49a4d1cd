"""Jitted wrappers for the edge_stream Pallas kernels (``interpret=None``
follows the platform, :func:`repro.kernels.interpret_mode`)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.state import ClusterState, FleetState, count_live_edges
from repro.graph.pipeline import PAD, pad_edges_to_chunks
from repro.kernels import interpret_mode
from repro.kernels.edge_stream.kernel import (
    GROUP_BYTES,
    LANES,
    build_call,
    build_decode_update_call,
    build_wavefront_call,
    edge_chunk,
    payload_groups,
    state_rows,
)


def _tiles(x, n: int):
    """``(T, n)`` -> the kernel's ``(T, rows, 128)`` int32 tile layout."""
    rows = state_rows(n)
    x = jnp.pad(x.astype(jnp.int32), ((0, 0), (0, rows * LANES - n)))
    return x.reshape(x.shape[0], rows, LANES)


def _stream_update(d, c, v, edges, v_max: int, chunk: int, interpret: bool):
    """Run ``(T, m, 2)`` edges through ``(T, n)`` states with the sequential
    kernel, one grid step per stream; returns the new ``(T, n)`` d, c, v."""
    T, n = d.shape
    m = edges.shape[1]
    chunk = edge_chunk(min(chunk, max(m, 1)))
    n_chunks = max(1, -(-m // chunk))
    padded = jnp.full((T, n_chunks * chunk, 2), PAD, jnp.int32)
    padded = jax.lax.dynamic_update_slice(
        padded, edges.astype(jnp.int32), (0, 0, 0)
    )
    call = build_call(T, n, chunk, n_chunks, int(v_max), interpret)
    out = call(
        padded.reshape(T, n_chunks, 2 * chunk // LANES, LANES),
        _tiles(d, n),
        _tiles(c, n),
        _tiles(v, n),
    )
    return tuple(x.reshape(T, -1)[:, :n] for x in out)


def _single_update(state, edges, v_max, chunk, interpret) -> ClusterState:
    """One stream (``T = 1``) through :func:`_stream_update`."""
    edges = edges.reshape(1, -1, 2)
    d, c, v = _stream_update(
        state.d[None], state.c[None], state.v[None], edges, v_max, chunk,
        interpret_mode(interpret),
    )
    return ClusterState(
        d=d[0],
        c=c[0],
        v=v[0],
        edges_seen=state.edges_seen + count_live_edges(edges[0], PAD),
    )


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "chunk", "interpret"),
    donate_argnums=(0,),
)
def pallas_update(
    state: ClusterState,
    edges: jax.Array,
    v_max: int,
    chunk: int = 2048,
    interpret: Optional[bool] = None,
) -> ClusterState:
    """State-threading in-VMEM Pallas tier: ingest ``edges`` into ``state``.

    Bit-exact with ``core.streaming.dense_update`` (strict stream order) —
    the kernel seeds its VMEM-resident (d, c, v) from ``state``, so
    arbitrary batch boundaries produce identical results.  ``state`` is
    donated (treat the passed-in state as consumed — the ``partial_fit``
    contract).
    """
    return _single_update(state, edges, v_max, chunk, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "chunk", "interpret"),
    donate_argnums=(0,),
)
def pallas_update_megabatch(
    state: ClusterState,
    edges: jax.Array,
    v_max: int,
    chunk: int = 2048,
    interpret: Optional[bool] = None,
) -> ClusterState:
    """Fused megabatch Pallas tier: ingest ``(K, B, 2)`` stacked batches in
    one kernel launch.

    The megabatch is flattened to ``K * B / chunk`` DMA chunks; the kernel
    keeps the 3n-int state in VMEM across all of them and streams chunk
    ``t+1`` from HBM while chunk ``t``'s sequential edge loop runs
    (``kernel.edge_stream_kernel``).  Strict stream order is preserved, so
    labels are bit-exact with per-batch :func:`pallas_update` — and with
    ``dense_update`` — for *any* ``K``/``B``; trailing all-PAD batches (a
    ragged tail megabatch) are no-ops.  ``state`` is donated.
    """
    return _single_update(state, edges, v_max, chunk, interpret)


@functools.partial(
    jax.jit, static_argnames=("v_max", "interpret"), donate_argnums=(0,)
)
def pallas_decode_update_megabatch(
    state: ClusterState,
    payload: jax.Array,
    desc: jax.Array,
    v_max: int,
    interpret: Optional[bool] = None,
) -> ClusterState:
    """Fused decode→update over one compressed megabatch — one dispatch.

    ``payload`` is the ``(P,)`` uint8 staging buffer and ``desc`` the
    ``(D, DESC_COLS)`` descriptor table of a validated
    :class:`~repro.graph.pipeline.CompressedMegaBatch`.  The payload is
    viewed as little-endian int32 words in ``(G, 8, 128)`` groups and left
    in HBM; ``kernel.edge_stream_decode_update_kernel`` DMAs each
    descriptor's byte columns into SMEM and decodes every row straight into
    the strict-order per-edge update, so the decoded edges never round-trip
    through HBM.  Labels are bit-exact with host decode +
    :func:`pallas_update_megabatch`.  ``state`` is donated.
    """
    n = state.d.shape[0]
    P = payload.shape[0]
    groups = payload_groups(P)
    words = jax.lax.bitcast_convert_type(
        payload.astype(jnp.uint8).reshape(P // 4, 4), jnp.int32
    )
    words = jnp.pad(words, (0, groups * GROUP_BYTES // 4 - P // 4))
    call = build_decode_update_call(
        n, groups, desc.shape[0], int(v_max), interpret_mode(interpret)
    )
    d, c, v, live = call(
        desc.astype(jnp.int32).reshape(-1),
        words.reshape(groups, -1, LANES),
        _tiles(state.d[None], n),
        _tiles(state.c[None], n),
        _tiles(state.v[None], n),
    )
    d, c, v = (x.reshape(-1)[:n] for x in (d, c, v))
    return ClusterState(d=d, c=c, v=v, edges_seen=state.edges_seen + live[0])


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "chunk", "interpret"),
    donate_argnums=(0,),
)
def pallas_wavefront_update(
    state: ClusterState,
    waves: jax.Array,
    leftover: jax.Array,
    meta: jax.Array,
    v_max: int,
    chunk: int = 2048,
    interpret: Optional[bool] = None,
):
    """Wavefront Pallas tier: ingest a planned megabatch (see
    ``repro.graph.wavefront.plan_waves``) in one kernel launch.

    ``waves`` is the planner's ``(n_waves, width, 2)`` layout, ``leftover``
    the ``(M, 2)`` uncovered suffix, ``meta`` the ``[n_waves_used,
    leftover_rows]`` loop bounds.  Labels are bit-identical to
    :func:`pallas_update_megabatch` over the original stream for any valid
    plan — vectorised waves with a runtime community-collision fallback
    (DESIGN.md §12).  Returns ``(state, stats)`` with ``stats =
    [live_waves, fallback_waves]``.  ``state`` is donated.  Mosaic refuses
    this kernel on a v5e (``kernel.py``), so on a TPU this raises the
    compiler's error.
    """
    n = state.d.shape[0]
    n_waves, width = waves.shape[0], waves.shape[1]
    padded, n_left_chunks = pad_edges_to_chunks(leftover, chunk)
    call = build_wavefront_call(
        n, width, n_waves, chunk, n_left_chunks, int(v_max),
        interpret_mode(interpret),
    )
    d, c, v, stats = call(
        waves.astype(jnp.int32),
        padded.reshape(n_left_chunks, chunk, 2),
        meta.astype(jnp.int32),
        state.d.astype(jnp.int32),
        state.c.astype(jnp.int32),
        state.v.astype(jnp.int32),
    )
    # waves + leftover hold exactly the live rows of the original megabatch
    seen = count_live_edges(waves.reshape(-1, 2), PAD) + count_live_edges(
        leftover, PAD
    )
    return (
        ClusterState(d=d, c=c, v=v, edges_seen=state.edges_seen + seen),
        stats,
    )


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "chunk", "interpret"),
    donate_argnums=(0,),
)
def pallas_fleet_update(
    state: FleetState,
    edges: jax.Array,
    v_max: int,
    chunk: int = 2048,
    interpret: Optional[bool] = None,
) -> FleetState:
    """Tenant-major fleet Pallas tier: ingest a ``(T, B, 2)`` staged slab
    into a ``(T, n)`` :class:`FleetState` in one kernel launch.

    The tenant axis is the kernel's grid — tenant ``t``'s d/c/v rows are
    DMA'd HBM→VMEM→HBM around its sequential per-edge loop
    (``kernel.edge_stream_kernel``), so every tenant row is bit-exact with
    ``core.streaming.dense_update`` over its own stream regardless of how
    the router grouped slabs into fleet steps.  ``state`` is donated (the
    ``partial_fit_fleet`` contract).
    """
    e = edges.astype(jnp.int32)
    d, c, v = _stream_update(
        state.d, state.c, state.v, e, v_max, chunk, interpret_mode(interpret)
    )
    live = (e[:, :, 0] != PAD) & (e[:, :, 1] != PAD) & (
        e[:, :, 0] != e[:, :, 1]
    )
    return FleetState(
        d=d,
        c=c,
        v=v,
        edges_seen=state.edges_seen + jnp.sum(live, axis=1, dtype=jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("v_max", "n", "chunk", "interpret")
)
def edge_stream_cluster(
    edges: jax.Array,
    v_max: int,
    n: int,
    chunk: int = 2048,
    interpret: Optional[bool] = None,
):
    """One-shot clustering with the in-VMEM Pallas kernel.

    .. deprecated:: use ``repro.cluster.cluster(..., backend="pallas")``.

    Args:
      edges: (m, 2) int32 stream (PAD rows are no-ops).
      v_max: paper's volume threshold.
      n: number of nodes (state = 3n int32 must fit VMEM:
        n ≤ ``kernel.MAX_NODES``).
      chunk: edges per HBM→SMEM DMA.
      interpret: Pallas interpret mode; ``None`` derives it from the
        platform (compiled on a TPU, interpreted elsewhere).

    Returns:
      (c, d, v) int32 arrays of size n — bit-exact with Algorithm 1.
    """
    s = pallas_update(
        ClusterState.init(n), edges, int(v_max), chunk=chunk,
        interpret=interpret,
    )
    return s.c, s.d, s.v
