"""Tier-2 chunked-batch streaming clustering (TPU-native, beyond-paper).

Processes the stream in fixed-size chunks.  All edges in a chunk read the
*pre-chunk* state ("Jacobi" semantics): decisions are computed vectorised on
the VPU, a node that several edges would move is moved by the first of them
in stream order, and state updates are applied with commutative scatter-adds.

This trades bit-exactness with the paper's strictly-sequential order for
parallelism; quality parity is *measured* in benchmarks (not assumed), and a
bit-exact serial-in-VMEM Pallas kernel is provided in
``repro.kernels.edge_stream`` for when exact semantics are required.  The
chunk size is part of the result (the Jacobi grouping), not a speed lever.

State layout: arrays of size ``n + 1`` — slot ``n`` is a write sink for
padded/no-op edges, so the inner loop is branch-free.  The public surface
takes/returns :class:`repro.core.state.ClusterState` (size ``n``); the sink
slot is an internal detail appended/stripped here, which costs about 0.1 ms
of a 2^20-edge dispatch at n = 2^22 on a TPU v5e.

Where the time goes: on a v5e a scatter into the n-sized state costs more
the more indices it has, whatever they are (distinct, repeated, dropped):
3.8 µs for 32, 13 µs for 128, 95 µs for 1,024 inside a scan.  So a chunk
costs its four 1,024-index degree and arrival scatters.  Its moves are few
(a median of 15 in 1,024 rows of a Graph500 stream past its first ~12,000
chunks) and are applied from a list compacted in stream order,
``MOVE_SLOTS`` winners a round (:func:`_moves`), not by chunk-wide scatters
that send every other row to the sink; only a chunk where more than half the
rows win (a fresh state, a sparse graph) takes the chunk-wide pass.  The
winners come from a compare over the chunk alone (:func:`_winners`), with no
n-sized array per chunk.  The fleet (``repro.core.fleet``) vmaps the same
scan.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.state import ClusterState, count_live_edges
from repro.graph.pipeline import PAD, pad_edges_to_chunks

Array = jax.Array


# Winners a chunk moves per round of its compacted move list (a module
# constant, not a knob).  Past its first ~11,000 chunks a Graph500 SCALE-22
# stream has at most 32 winners in a 1,024-row chunk (median 15); earlier
# chunks take more rounds.  32 was the fastest of 16, 32, 64 and 128 there
# on a TPU v5e.
MOVE_SLOTS = 32


def _winners(mover, sink):
    """``win[p]``: row ``p`` moves a node (not the sink) that no earlier row
    of the chunk moves — first in stream order wins.  A ``B x B`` compare
    over the chunk alone: no array of the state's size is made."""
    order = jnp.arange(mover.shape[0], dtype=jnp.int32)
    earlier = (mover[:, None] == mover[None, :]) & (order[None, :] < order[:, None])
    return (mover != sink) & ~jnp.any(earlier, axis=1)


def _moves(dcv, win, mover, target, src, sink):
    """Apply the winners' moves, bit for bit as one chunk-wide pass would:
    winners move distinct nodes, ``d`` is final for the chunk, and int32 adds
    commute.

    The first ``MOVE_SLOTS`` winners, in stream order, are gathered into a
    list: one scatter-add of ``+d[mover]`` and ``-d[mover]`` into the
    ``2 * MOVE_SLOTS`` volumes of targets and sources, and one label set
    over ``MOVE_SLOTS`` movers.  An empty slot ``k`` indexes ``n + 1 + k``,
    past the state, and is dropped.  A chunk with more winners takes more
    such rounds, or, past half the chunk's rows, moves the rest at once with
    chunk-wide scatters, where the other rows write no-ops into the sink
    slot.  On a TPU v5e at n = 2^22 the rounds add about 0.35 µs per winner
    to a 1,024-row chunk and the wide pass about 180-235 µs; they meet near
    585 winners.

    The wide pass is a ``while_loop`` of at most one trip, not a ``cond``:
    the v5e compiler keeps the state in VMEM across the scan only without
    the ``cond``.  Under the fleet's ``vmap`` a loop whose tenants disagree
    selects over the whole ``(T, n)`` state on each trip."""
    d, c, v = dcv
    B = win.shape[0]
    seen = jnp.cumsum(win.astype(jnp.int32))
    total = seen[-1]
    k = jnp.arange(MOVE_SLOTS, dtype=jnp.int32)
    empty = d.shape[0] + k

    def round_(carry):
        r, c, v = carry
        # winner r * MOVE_SLOTS + k sits at the first row where more than
        # that many winners have been seen
        first = r * MOVE_SLOTS + k
        row = jnp.sum(seen[None, :] <= first[:, None], axis=1, dtype=jnp.int32)
        full = row < B
        row = jnp.minimum(row, B - 1)
        mover_k = jnp.where(full, mover[row], empty)
        target_k = jnp.where(full, target[row], empty)
        src_k = jnp.where(full, src[row], empty)
        dm = d.at[mover_k].get(mode="fill", fill_value=0)
        v = v.at[jnp.concatenate([target_k, src_k])].add(
            jnp.concatenate([dm, -dm]), mode="drop"
        )
        c = c.at[mover_k].set(target_k, mode="drop", unique_indices=True)
        return r + 1, c, v

    def wide(carry):
        _, c, v = carry
        rest = win & (seen > MOVE_SLOTS)
        mover_w = jnp.where(rest, mover, sink)
        dm = jnp.where(rest, d[mover_w], 0)
        v = v.at[jnp.where(rest, target, sink)].add(dm)
        v = v.at[jnp.where(rest, src, sink)].add(-dm)
        c = c.at[mover_w].set(jnp.where(rest, target, c[mover_w]))
        return False, c, v

    # round 0 outside the loops: nearly every chunk has a winner
    r, c, v = round_((jnp.int32(0), c, v))
    many = total > B // 2
    _, c, v = jax.lax.while_loop(lambda carry: carry[0], wide, (many, c, v))
    _, c, v = jax.lax.while_loop(
        lambda carry: ~many & (carry[0] * MOVE_SLOTS < total), round_, (r, c, v)
    )
    return d, c, v


def _chunk_update(state, chunk, *, v_max: int, n: int):
    """Apply one chunk (B, 2) of edges with Jacobi semantics."""
    d, c, v = state  # each (n + 1,)
    i_raw, j_raw = chunk[:, 0], chunk[:, 1]
    live = (i_raw != PAD) & (j_raw != PAD) & (i_raw != j_raw)
    sink = jnp.int32(n)
    i = jnp.where(live, i_raw, sink)
    j = jnp.where(live, j_raw, sink)
    one = live.astype(jnp.int32)

    # Degree update — commutative, exact regardless of intra-chunk order.
    d = d.at[i].add(one).at[j].add(one)

    ci = c[i]
    cj = c[j]
    # Arrival volume update (+1 per endpoint community, labels frozen).
    v = v.at[ci].add(one).at[cj].add(one)

    vci = v[ci]
    vcj = v[cj]
    ok = live & (vci <= v_max) & (vcj <= v_max)
    i_joins = ok & (vci <= vcj)
    j_joins = ok & (vci > vcj)

    mover = jnp.where(i_joins, i, jnp.where(j_joins, j, sink))
    target = jnp.where(i_joins, cj, ci)
    src = jnp.where(i_joins, ci, cj)
    return _moves((d, c, v), _winners(mover, sink), mover, target, src, sink), ()


def _scan_chunks(
    state: ClusterState, chunks: Array, v_max: Array, n: int
) -> ClusterState:
    """Scan the Jacobi chunk update over ``(n_chunks, chunk, 2)`` edges —
    the shared core of the per-batch and fused megabatch entry points (one
    compile, ``n_chunks`` chunk steps per dispatch)."""
    init = (
        jnp.concatenate([state.d.astype(jnp.int32), jnp.int32([0])]),
        jnp.concatenate([state.c.astype(jnp.int32), jnp.int32([n])]),
        jnp.concatenate([state.v.astype(jnp.int32), jnp.int32([0])]),
    )
    (d, c, v), _ = jax.lax.scan(
        functools.partial(_chunk_update, v_max=jnp.int32(v_max), n=n), init, chunks
    )
    return ClusterState(
        d=d[:n],
        c=c[:n],
        v=v[:n],
        edges_seen=state.edges_seen + count_live_edges(chunks.reshape(-1, 2), PAD),
    )


@functools.partial(
    jax.jit, static_argnames=("chunk",), donate_argnums=(0,)
)
def chunked_update(
    state: ClusterState, edges: Array, v_max: Array, chunk: int = 1024
) -> ClusterState:
    """State-threading chunked tier: ingest ``edges`` into ``state``.

    ``edges``: (m, 2) int32 (PAD-padded ok); the batch is padded up to a
    multiple of ``chunk`` internally, and PAD edges are no-ops — but note the
    *grouping* of edges into Jacobi chunks restarts at every call, so batch
    boundaries are chunk boundaries (deterministic, batching-dependent).

    ``state`` is *donated*: on accelerator backends its buffers are reused
    for the output (no per-step 3n-int copy), so callers must treat the
    passed-in state as consumed — exactly the ``partial_fit`` contract,
    which replaces its state with the returned one.
    """
    n = state.d.shape[0]
    padded, n_chunks = pad_edges_to_chunks(edges, chunk)
    return _scan_chunks(state, padded.reshape(n_chunks, chunk, 2), v_max, n)


@functools.partial(
    jax.jit, static_argnames=("chunk",), donate_argnums=(0,)
)
def chunked_update_megabatch(
    state: ClusterState, edges: Array, v_max: Array, chunk: int = 1024
) -> ClusterState:
    """Fused megabatch chunked tier: ingest ``(K, B, 2)`` stacked batches in
    *one* dispatch.

    The K batches are flattened and scanned as one ``lax.scan`` over
    ``K * B / chunk`` Jacobi chunks — when ``B`` is a multiple of ``chunk``
    (guaranteed for pipeline-staged megabatches: the ``BatchPipeline`` rounds
    its batch size up to the chunk for chunk-aligned backends), the chunk
    grouping is identical to ``K`` sequential :func:`chunked_update` calls,
    so labels are bit-identical to the per-batch path while dispatch/transfer
    overhead drops ~K-fold.  All-PAD trailing batches (a ragged tail
    megabatch) are no-ops.  ``state`` is donated, as in
    :func:`chunked_update`.
    """
    n = state.d.shape[0]
    K, B = edges.shape[0], edges.shape[1]
    padded, n_chunks = pad_edges_to_chunks(edges.reshape(K * B, 2), chunk)
    return _scan_chunks(state, padded.reshape(n_chunks, chunk, 2), v_max, n)


@functools.partial(
    jax.jit, static_argnames=("v_max", "n", "chunk"), donate_argnums=(4, 5)
)
def cluster_stream_chunked(
    edges: Array,
    v_max: int,
    n: int,
    chunk: int = 1024,
    init_d: Array | None = None,
    init_v: Array | None = None,
) -> Tuple[Array, Array, Array]:
    """One-shot chunked streaming clustering.  Returns ``(c, d, v)`` size n.

    .. deprecated:: use ``repro.cluster.cluster(..., backend="chunked")``.

    ``init_d`` / ``init_v`` (size n) seed the degree/volume state — used by the
    distributed merge phase to carry supernode internal mass into the
    contracted stream.
    """
    state = ClusterState.init(n)
    if init_d is not None:
        state.d = init_d.astype(jnp.int32)
    if init_v is not None:
        state.v = init_v.astype(jnp.int32)
    s = chunked_update(state, edges, jnp.int32(v_max), chunk=chunk)
    return s.c, s.d, s.v
