"""Device-side wavefront apply: vectorised waves, sequential fallback.

Shared by the pure-JAX reference path (:func:`wavefront_update_megabatch`,
used off a TPU and as the oracle for the kernel) and the
Pallas wavefront kernel (``repro.kernels.edge_stream``), which imports
:func:`wave_conflict` / :func:`wave_apply` so both paths apply *exactly*
the same math (DESIGN.md §12).

Correctness argument, cell by cell.  The planner guarantees every wave is
a contiguous, node-disjoint run of the stream:

* ``d[i]``, ``c[i]`` are node-indexed — node-disjointness alone makes the
  wave's reads/writes of them conflict-free.
* ``v[c]`` and the join decisions read community volumes, and communities
  are dynamic — so the wave needs *community* disjointness too, decidable
  only at apply time against the live state.  :func:`wave_conflict` flags
  a wave when two live edges touch the same **unsaturated** community
  (``v[c] < v_max`` before the wave).  A *saturated* shared community is
  provably harmless: no edge touching it can pass the ``ok`` volume test
  in any order (every reader sees at least ``v_max + 1``), so it only ever
  receives commutative arrival ``+1``s and is never a join source/target —
  the final state is order-independent.  This is what keeps the fallback
  rate low in steady state, where most communities sit at the cap.

Flagged waves fall back to the sequential per-edge loop, so labels are
bit-identical to ``cluster_stream_dense`` for every stream and every plan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.state import ClusterState, count_live_edges
from repro.core.streaming import _edge_update
from repro.graph.pipeline import PAD


def wave_live(i_raw, j_raw):
    """Per-slot liveness mask (PAD rows and self-loops are no-ops)."""
    return (i_raw != PAD) & (j_raw != PAD) & (i_raw != j_raw)


def wave_conflict(c, v, i_raw, j_raw, v_max, n):
    """True iff the vectorised apply of this node-disjoint wave could
    diverge from the sequential order: some unsaturated community is
    touched by more than one live edge.

    Dead slots and saturated communities are keyed by unique sentinels
    ``>= n`` (labels live in ``[0, n)``), so a duplicate among the sorted
    keys is exactly a real collision.  An edge whose endpoints share one
    community contributes that community once — a single edge always
    commutes with itself.
    """
    W = i_raw.shape[0]
    live = wave_live(i_raw, j_raw)
    i = jnp.maximum(i_raw, 0)
    j = jnp.maximum(j_raw, 0)
    ci = c[i]
    cj = c[j]
    e = jnp.arange(W, dtype=jnp.int32)
    hot_i = live & (v[ci] < v_max)
    hot_j = live & (v[cj] < v_max) & (cj != ci)
    key_i = jnp.where(hot_i, ci, n + 2 * e)
    key_j = jnp.where(hot_j, cj, n + 2 * e + 1)
    keys = jnp.sort(jnp.concatenate([key_i, key_j]))
    return jnp.any(keys[1:] == keys[:-1])


def wave_apply(d, c, v, i_raw, j_raw, v_max):
    """Apply one wave as gathered vector loads / scattered stores.

    Bit-exact with the sequential loop exactly when
    :func:`wave_conflict` is False (node-disjoint wave, no shared
    unsaturated community): every gather then sees the same values the
    sequential order would, and the scatters hit disjoint cells — except
    the commutative ``+1`` arrivals on saturated shared communities, whose
    order never mattered.
    """
    n = d.shape[0]
    live = wave_live(i_raw, j_raw)
    i = jnp.maximum(i_raw, 0)
    j = jnp.maximum(j_raw, 0)
    one = jnp.where(live, jnp.int32(1), jnp.int32(0))

    d = d.at[i].add(one).at[j].add(one)
    ci = c[i]
    cj = c[j]
    # both arrivals land before any read, matching the sequential reload
    # (an edge with ci == cj sees +2, like the scalar path)
    v = v.at[ci].add(one).at[cj].add(one)
    vci = v[ci]
    vcj = v[cj]

    ok = live & (vci <= v_max) & (vcj <= v_max)
    i_joins = ok & (vci <= vcj)
    j_joins = ok & (vci > vcj)
    win = i_joins | j_joins

    mover = jnp.where(i_joins, i, j)
    target = jnp.where(i_joins, cj, ci)
    source = jnp.where(i_joins, ci, cj)
    dm = jnp.where(win, d[mover], 0)
    v = v.at[target].add(dm).at[source].add(-dm)
    # non-winning slots are routed out of bounds and dropped — clamping to
    # a real index would collide with a genuine write to that node
    c = c.at[jnp.where(win, mover, n)].set(target, mode="drop")
    return d, c, v


def _sequential_rows(dcv, rows, v_max):
    """The fallback: the scan tier's per-edge step over ``rows`` in order."""
    (d, c, v), _ = jax.lax.scan(
        functools.partial(_edge_update, v_max=v_max), dcv, rows
    )
    return d, c, v


@functools.partial(jax.jit, donate_argnums=(0,))
def wavefront_update_megabatch(
    state: ClusterState, waves, leftover, meta, v_max
) -> tuple:
    """Reference wavefront ingest over a :class:`~repro.graph.wavefront
    .WavePlan`'s arrays: vector-apply each wave, sequential fallback on
    community collision, then drain the uncovered suffix sequentially.

    Bit-exact with ``dense_update`` over the original stream for any plan
    produced by ``plan_waves`` (hypothesis-pinned in
    ``tests/test_wavefront.py``).  Only ``meta[0]`` waves are visited (a
    ``fori_loop``, not a full-buffer scan), so the planner's slack budget
    costs staging memory but never device compute.  Returns ``(new_state,
    stats)`` with ``stats = [live_waves, fallback_waves]`` int32.
    ``state`` is donated.
    """
    n = state.d.shape[0]
    v_max = jnp.int32(v_max)
    waves = waves.astype(jnp.int32)
    leftover = leftover.astype(jnp.int32)
    init = (
        state.d.astype(jnp.int32),
        state.c.astype(jnp.int32),
        state.v.astype(jnp.int32),
        jnp.zeros((2,), jnp.int32),
    )

    def step(t, carry):
        d, c, v, stats = carry
        wave = jax.lax.dynamic_index_in_dim(waves, t, keepdims=False)
        i_raw = wave[:, 0]
        j_raw = wave[:, 1]
        has_live = jnp.any(wave_live(i_raw, j_raw))
        conflict = wave_conflict(c, v, i_raw, j_raw, v_max, n)
        d, c, v = jax.lax.cond(
            conflict,
            lambda dcv: _sequential_rows(dcv, wave, v_max),
            lambda dcv: wave_apply(*dcv, i_raw, j_raw, v_max),
            (d, c, v),
        )
        stats = stats + jnp.stack(
            [has_live.astype(jnp.int32), (conflict & has_live).astype(jnp.int32)]
        )
        return d, c, v, stats

    nw = jnp.minimum(meta[0].astype(jnp.int32), waves.shape[0])
    d, c, v, stats = jax.lax.fori_loop(0, nw, step, init)

    # skip the O(M) sequential suffix scan entirely in the common case
    # where the plan covered every row (live rows always have i != PAD)
    has_left = jnp.any(leftover[:, 0] != PAD)
    d, c, v = jax.lax.cond(
        has_left,
        lambda dcv: _sequential_rows(dcv, leftover, v_max),
        lambda dcv: dcv,
        (d, c, v),
    )
    seen = count_live_edges(waves.reshape(-1, 2), PAD) + count_live_edges(
        leftover, PAD
    )
    return (
        ClusterState(d=d, c=c, v=v, edges_seen=state.edges_seen + seen),
        stats,
    )
