"""The chunked tier's move phase against a plain numpy Jacobi step.

``chunked_update`` applies each chunk's moves from a list compacted in
stream order, ``MOVE_SLOTS`` winners a round; a chunk with more winners
takes more rounds, and one where more than half the rows win moves the rest
with chunk-wide scatters.  The reference below is written from the rule alone:
every row of a chunk reads the state before the chunk, degrees and
arrival volumes are added with ``np.add.at``, and where several rows would
move one node the first row in stream order moves it.  Each case states
how many winners its chunks have, so the compacted path (at most one round),
its edge (``MOVE_SLOTS`` and ``MOVE_SLOTS + 1``), the last many-round chunk
(half the rows), the first wide one (one more) and a fresh state are each
pinned to the reference bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chunked import MOVE_SLOTS, chunked_update, chunked_update_megabatch
from repro.core.state import ClusterState

CHUNK = 1024
V_MAX = 64
N = 4096
PAD = -1


def reference(d, c, v, edges, v_max, chunk=CHUNK):
    """Jacobi Algorithm 1 over ``chunk``-row chunks of ``edges`` (PAD rows
    pad the tail).  Returns the new ``d, c, v``, the live rows and the
    winners of each chunk."""
    d, c, v = d.copy(), c.copy(), v.copy()
    m = len(edges)
    rows = np.full((-(-max(m, 1) // chunk) * chunk, 2), PAD, np.int64)
    rows[:m] = edges
    winners, live_rows = [], 0
    for start in range(0, len(rows), chunk):
        i, j = rows[start : start + chunk].T
        live = (i != PAD) & (j != PAD) & (i != j)
        i, j = i[live], j[live]
        live_rows += int(live.sum())
        np.add.at(d, i, 1)
        np.add.at(d, j, 1)
        ci, cj = c[i], c[j]
        np.add.at(v, ci, 1)
        np.add.at(v, cj, 1)
        vci, vcj = v[ci], v[cj]
        moves = {}
        for e in range(len(i)):
            if vci[e] > v_max or vcj[e] > v_max:
                continue
            if vci[e] <= vcj[e]:
                mover, target, src = i[e], cj[e], ci[e]
            else:
                mover, target, src = j[e], ci[e], cj[e]
            moves.setdefault(mover, (target, src))
        for mover, (target, src) in moves.items():
            v[target] += d[mover]
            v[src] -= d[mover]
            c[mover] = target
        winners.append(len(moves))
    return d, c, v, live_rows, winners


def _fresh():
    return np.zeros(N, np.int64), np.arange(N, dtype=np.int64), np.zeros(N, np.int64)


def _warm(free):
    """Nodes in groups of 8 with volume 1,000 (past ``V_MAX``: they never
    move nor take a node), except ``free`` singletons of volume 0."""
    d = np.full(N, 125, np.int64)
    c = np.arange(N, dtype=np.int64) // 8 * 8
    v = np.zeros(N, np.int64)
    v[::8] = 1000
    d[free], c[free], v[free] = 0, free, 0
    return d, c, v


def _rows(rng, k_free_pairs, filler=CHUNK):
    """One chunk: ``k_free_pairs`` rows joining disjoint pairs of free
    singletons (each moves its first node), spread among rows between
    full groups (which move nothing).  Free nodes are the odd ids."""
    free = np.arange(1, N, 2)
    pairs = rng.choice(free, size=(k_free_pairs, 2), replace=False)
    full = rng.choice(np.arange(0, N, 2), size=(filler - k_free_pairs, 2))
    rows = np.concatenate([pairs, full])
    return rows[rng.permutation(len(rows))], free


def case_fresh(rng):
    # every row joins two untouched singletons: every live row wins
    e = rng.choice(N, size=(CHUNK, 2), replace=False)
    return _fresh(), e, lambda w: w[0] == CHUNK > MOVE_SLOTS


def case_warm(rng):
    e, free = _rows(rng, 15)
    return _warm(free), e, lambda w: w == [15]


def case_exactly_slots(rng):
    e, free = _rows(rng, MOVE_SLOTS)
    return _warm(free), e, lambda w: w == [MOVE_SLOTS]


def case_slots_plus_one(rng):
    e, free = _rows(rng, MOVE_SLOTS + 1)
    return _warm(free), e, lambda w: w == [MOVE_SLOTS + 1]


def case_half_chunk(rng):
    e, free = _rows(rng, CHUNK // 2)
    return _warm(free), e, lambda w: w == [CHUNK // 2]


def case_half_chunk_plus_one(rng):
    e, free = _rows(rng, CHUNK // 2 + 1)
    return _warm(free), e, lambda w: w == [CHUNK // 2 + 1]


def case_no_winners(rng):
    loops = np.repeat(rng.integers(0, N, CHUNK // 2)[:, None], 2, axis=1)
    e = np.concatenate([loops, np.full((CHUNK - len(loops), 2), PAD)])
    return _fresh(), e[rng.permutation(CHUNK)], lambda w: w == [0]


def case_one_mover_many_rows(rng):
    # node x (a free singleton) would join each of ten groups of volume 20;
    # the first of its rows in stream order decides where it goes
    d, c, v = _warm(np.arange(1, N, 2))
    x, groups = 1, np.arange(16, 16 + 10 * 8, 8)
    v[groups], d[groups + 2] = 20, 20
    mine = np.stack([np.full(10, x), groups + 2], axis=1)
    e, _ = _rows(rng, 20, filler=CHUNK - 10)
    e = e[(e != x).all(axis=1) & ~np.isin(c[e], groups).any(axis=1)]
    rows = np.empty((len(e) + 10, 2), np.int64)
    mine_at = np.zeros(len(rows), bool)
    mine_at[rng.choice(len(rows), size=10, replace=False)] = True
    rows[mine_at], rows[~mine_at] = mine, e
    return (d, c, v), rows, lambda w: 0 < w[0] <= MOVE_SLOTS, (x, groups[0])


def case_ragged_tail(rng):
    # a fresh stream of 2 chunks and 37 rows: chunks past one round, then
    # a PAD-padded last chunk
    e = rng.integers(0, N, size=(2 * CHUNK + 37, 2))
    return _fresh(), e, lambda w: len(w) == 3 and w[0] > MOVE_SLOTS


CASES = {
    "fresh_all_win": case_fresh,
    "warm_few_winners": case_warm,
    "exactly_slots": case_exactly_slots,
    "slots_plus_one": case_slots_plus_one,
    "half_chunk": case_half_chunk,
    "half_chunk_plus_one": case_half_chunk_plus_one,
    "self_loops_and_pad": case_no_winners,
    "one_mover_many_rows": case_one_mover_many_rows,
    "ragged_tail": case_ragged_tail,
}


def _run(entry, state, edges):
    d, c, v = (jnp.asarray(a, jnp.int32) for a in state)
    s = ClusterState(d=d, c=c, v=v, edges_seen=jnp.int32(0))
    e = jnp.asarray(edges, jnp.int32)
    if entry == "per_batch":
        return chunked_update(s, e, jnp.int32(V_MAX), chunk=CHUNK)
    k = -(-len(edges) // CHUNK)
    stacked = jnp.full((k * CHUNK, 2), PAD, jnp.int32).at[: len(edges)].set(e)
    return chunked_update_megabatch(
        s, stacked.reshape(k, CHUNK, 2), jnp.int32(V_MAX), chunk=CHUNK
    )


@pytest.mark.parametrize("entry", ["per_batch", "megabatch"])
@pytest.mark.parametrize("name", list(CASES))
def test_moves_match_numpy_jacobi(name, entry):
    rng = np.random.default_rng(sorted(CASES).index(name))
    state, edges, winners_ok, *pin = CASES[name](rng)
    d, c, v, live, winners = reference(*state, edges, V_MAX)
    assert winners_ok(winners), winners
    got = _run(entry, state, edges)
    assert np.array_equal(np.asarray(got.d), d)
    assert np.array_equal(np.asarray(got.c), c)
    assert np.array_equal(np.asarray(got.v), v)
    assert int(got.edges_seen) == live
    if pin:
        (x, first_target), = pin
        assert c[x] == first_target
