"""The paper's Algorithm 1 — one-pass edge-streaming graph clustering.

Three tiers (see DESIGN.md §3):

* :func:`oracle_update` — bit-faithful dictionary implementation of
  Algorithm 1 (the paper-faithful baseline; pure Python/numpy).
* :func:`dense_update` — dense-array variant where a node's initial
  community index is its own node id (behaviourally identical up to community
  relabeling; this is the layout every JAX/Pallas tier uses).
* :func:`scan_update` — ``jax.lax.scan`` port, one edge per step, bit-exact
  with the dense oracle.

Each tier takes and returns a :class:`repro.core.state.ClusterState` — the
paper's ``3n`` integers per node (degree ``d``, community ``c``, community
volume ``v``) plus an edges-seen counter — so a stream can be ingested in
arbitrary batches and suspended/resumed (``repro.cluster.StreamClusterer``).

The historical one-shot entry points (``cluster_stream_oracle``,
``cluster_stream_dense``, ``cluster_stream_scan``) are retained as thin
shims over the state-threading tiers.

.. deprecated::
   Call sites should use :func:`repro.cluster.cluster` /
   :class:`repro.cluster.StreamClusterer` with ``ClusterConfig(backend=...)``
   instead of these per-tier functions.

Tie rule: Algorithm 1 line 11 — ``v[c_i] <= v[c_j]`` ⇒ *i joins the community
of j*.  (The paper's §2.3 prose states the opposite tie-break; we follow the
pseudocode, which is what the reference C++ implementation does.)
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import ClusterState, count_live_edges
from repro.graph.pipeline import PAD
from repro.obs import span

Array = jax.Array


# ---------------------------------------------------------------------------
# Tier 0a: faithful dictionary oracle (paper's Algorithm 1, line by line)
# ---------------------------------------------------------------------------

def _oracle_loop(
    d: Dict[int, int],
    v: Dict[int, int],
    c: Dict[int, int],
    k: int,
    edges: np.ndarray,
    v_max: int,
) -> Tuple[int, int]:
    """Algorithm 1 inner loop on the paper's dictionaries.

    Returns ``(next_k, live_edges_processed)``."""
    seen = 0
    for i, j in np.asarray(edges):
        i, j = int(i), int(j)
        if i == PAD or j == PAD or i == j:
            continue
        seen += 1
        if c.get(i, 0) == 0:
            c[i] = k
            k += 1
        if c.get(j, 0) == 0:
            c[j] = k
            k += 1
        d[i] = d.get(i, 0) + 1
        d[j] = d.get(j, 0) + 1
        v[c[i]] = v.get(c[i], 0) + 1
        v[c[j]] = v.get(c[j], 0) + 1
        if v[c[i]] <= v_max and v[c[j]] <= v_max:
            if v[c[i]] <= v[c[j]]:  # i joins the community of j
                v[c[j]] += d[i]
                v[c[i]] -= d[i]
                c[i] = c[j]
            else:  # j joins the community of i
                v[c[i]] += d[j]
                v[c[j]] -= d[j]
                c[j] = c[i]
    return k, seen


def oracle_update(
    state: ClusterState, edges: np.ndarray, v_max: int
) -> ClusterState:
    """State-threading dict oracle (paper label space, resumable).

    Layout (see :class:`ClusterState`): ``c[i] = 0`` means node ``i`` has
    never appeared; community ids are 1-based and ``v`` is stored shifted by
    one (``v[k - 1]`` is the volume of community ``k``).  Fresh state must be
    created with ``c`` zeroed — use ``oracle_init(n)``.
    """
    s = state.to_numpy()
    c = {i: int(lab) for i, lab in enumerate(s.c) if lab != 0}
    d = {i: int(deg) for i, deg in enumerate(s.d) if deg != 0}
    v = {kk: int(vol) for kk, vol in enumerate(np.asarray(s.v), start=1) if vol != 0}
    # Every node gets a fresh id exactly once, so the next id is one past the
    # number of ever-seen nodes (max(c) would wrongly reuse absorbed ids).
    k = int(np.count_nonzero(np.asarray(s.c))) + 1
    _, seen = _oracle_loop(d, v, c, k, edges, v_max)
    out = ClusterState.init(s.n, numpy=True)
    out.c[:] = 0
    for i, lab in c.items():
        out.c[i] = lab
    for i, deg in d.items():
        out.d[i] = deg
    for kk, vol in v.items():
        out.v[kk - 1] = vol
    out.edges_seen = s.edges_seen + seen
    return out


def oracle_init(n: int) -> ClusterState:
    """Fresh state in the dict-oracle label space (all nodes unassigned)."""
    s = ClusterState.init(n, numpy=True)
    s.c[:] = 0
    return s


def cluster_stream_oracle(edges: np.ndarray, v_max: int) -> Dict[int, int]:
    """One-shot Algorithm 1, dictionaries with default 0, community ids 1,2,...

    .. deprecated:: use ``repro.cluster.cluster(..., backend="oracle")``.

    Args:
      edges: int array of shape (m, 2); rows are stream order.
      v_max: volume threshold parameter (``>= 1``).

    Returns:
      dict node id -> community id.
    """
    d: Dict[int, int] = {}
    v: Dict[int, int] = {}
    c: Dict[int, int] = {}
    _oracle_loop(d, v, c, 1, edges, v_max)
    return c


# ---------------------------------------------------------------------------
# Tier 0b: dense-array oracle (initial community of node i is i)
# ---------------------------------------------------------------------------

def dense_update(
    state: ClusterState, edges: np.ndarray, v_max: int
) -> ClusterState:
    """State-threading dense-layout Algorithm 1 (numpy loop, resumable).

    Community ids live in the node-id space (the founding node's id).  This
    is a pure relabeling of the paper's incrementing-``k`` scheme: only
    equality of community ids and the volumes ``v`` enter the decision rule,
    and both are preserved.  Verified against :func:`oracle_update` in tests.
    """
    s = state.to_numpy()
    d, c, v = s.d.copy(), s.c.copy(), s.v.copy()
    seen = 0
    for i, j in np.asarray(edges):
        i, j = int(i), int(j)
        if i == PAD or j == PAD or i == j:
            continue
        seen += 1
        d[i] += 1
        d[j] += 1
        ci, cj = c[i], c[j]
        v[ci] += 1
        v[cj] += 1
        if v[ci] <= v_max and v[cj] <= v_max:
            if v[ci] <= v[cj]:  # i joins the community of j
                v[cj] += d[i]
                v[ci] -= d[i]
                c[i] = cj
            else:  # j joins the community of i
                v[ci] += d[j]
                v[cj] -= d[j]
                c[j] = ci
    return ClusterState(d=d, c=c, v=v, edges_seen=s.edges_seen + seen)


def cluster_stream_dense(
    edges: np.ndarray, v_max: int, n: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-shot dense-layout Algorithm 1.  Returns ``(c, d, v)`` int64 arrays.

    .. deprecated:: use ``repro.cluster.cluster(..., backend="dense")``.
    """
    s = dense_update(ClusterState.init(n, numpy=True), edges, v_max)
    return (
        s.c.astype(np.int64),
        s.d.astype(np.int64),
        s.v.astype(np.int64),
    )


# ---------------------------------------------------------------------------
# Tier 1: jax.lax.scan port (bit-exact with the dense oracle)
# ---------------------------------------------------------------------------

def _edge_update(state, edge, *, v_max):
    """One Algorithm-1 step on dense (d, c, v) int32 state."""
    d, c, v = state
    i, j = edge[0], edge[1]
    live = (i != PAD) & (j != PAD) & (i != j)
    # Clamp so gathers stay in bounds for padded edges (updates are masked).
    i = jnp.maximum(i, 0)
    j = jnp.maximum(j, 0)
    one = jnp.where(live, jnp.int32(1), jnp.int32(0))

    d = d.at[i].add(one).at[j].add(one)
    di, dj = d[i], d[j]
    ci, cj = c[i], c[j]
    # Chained .at updates have sequential semantics, so ci == cj gets +2.
    v = v.at[ci].add(one).at[cj].add(one)
    vci, vcj = v[ci], v[cj]

    ok = live & (vci <= v_max) & (vcj <= v_max)
    i_joins = ok & (vci <= vcj)
    j_joins = ok & (vci > vcj)

    move_i = jnp.where(i_joins, di, 0)
    move_j = jnp.where(j_joins, dj, 0)
    v = v.at[cj].add(move_i - move_j).at[ci].add(move_j - move_i)
    c = c.at[i].set(jnp.where(i_joins, cj, ci))
    c = c.at[j].set(jnp.where(j_joins, ci, c[j]))
    return (d, c, v), ()


@jax.jit
def scan_update(state: ClusterState, edges: Array, v_max: Array) -> ClusterState:
    """State-threading ``lax.scan`` tier (one edge per step, resumable).

    Sequential by construction — bit-exact with :func:`dense_update`; used as
    the on-device oracle and for small graphs.  Large graphs use the chunked
    tier (``core.chunked``) or the Pallas kernel (``kernels.edge_stream``).
    """
    edges = edges.astype(jnp.int32)
    init = (
        state.d.astype(jnp.int32),
        state.c.astype(jnp.int32),
        state.v.astype(jnp.int32),
    )
    (d, c, v), _ = jax.lax.scan(
        functools.partial(_edge_update, v_max=jnp.int32(v_max)), init, edges
    )
    return ClusterState(
        d=d, c=c, v=v, edges_seen=state.edges_seen + count_live_edges(edges, PAD)
    )


@functools.partial(jax.jit, static_argnames=("v_max", "n"))
def cluster_stream_scan(edges: Array, v_max: int, n: int):
    """One-shot ``lax.scan`` tier; state = 3n int32 (paper footprint).

    .. deprecated:: use ``repro.cluster.cluster(..., backend="scan")``.

    Returns ``(c, d, v)``.
    """
    s = scan_update(ClusterState.init(n), edges, jnp.int32(v_max))
    return s.c, s.d, s.v


def canonical_labels(c: np.ndarray) -> np.ndarray:
    """Map community labels to 0..K-1 by first appearance (for comparisons).

    Labels in a dense range -- integers in ``0..hi`` with ``hi < 2 * c.size``,
    as every on-device tier's node ids are -- take an O(n) pass with no sort:
    a reversed scatter of positions gives each label some position of its own
    (the first, where NumPy's last write wins), and a check that no element
    lies before its label's position proves it the first one.  Each element's
    rank is then the count of first positions up to its label's.  NumPy does
    not promise which of repeated writes wins, so where the check fails, and
    for any other labels (negative, ``2 * c.size`` or more, not integers, or
    none), ``np.unique`` sorts them inside the ``repro.labels.sort`` span.
    Both paths give the same ``intp`` labels.  The scatter is one sequential
    pass; the gathers, the check and the counts run over blocks of
    ``_BLOCK`` elements on up to ``_THREADS`` threads, since NumPy releases
    the GIL inside each.
    """
    c = np.asarray(c)
    if c.size and np.issubdtype(c.dtype, np.integer):
        lo, hi = int(c.min()), int(c.max())
        if lo >= 0 and hi + 1 <= 2 * c.size:
            idx = c.reshape(-1)
            if not np.can_cast(idx.dtype, np.intp):  # np.take refuses uint64
                idx = idx.astype(np.intp)
            rank = _first_appearance_ranks(idx, _first_positions(idx, hi + 1))
            if rank is not None:
                return rank.reshape(c.shape)
    with span("repro.labels.sort"):
        _, first, inv = np.unique(c, return_index=True, return_inverse=True)
        order = np.argsort(first, kind="stable")
        rank = np.empty_like(order)
        rank[order] = np.arange(order.shape[0])
        return rank[inv]


_BLOCK = 1 << 20  # elements per task of canonical_labels' threaded passes
_THREADS = 8  # the passes are bound by memory: a few threads take the gain


def _first_positions(idx: np.ndarray, labels: int) -> np.ndarray:
    """``first[k]``: a position of label ``k`` in ``idx``, for each ``k`` in
    ``idx``; the other entries are never written.  Written last to first, so
    the first occurrence is the last write."""
    pos = np.arange(idx.size, dtype=np.int32 if idx.size < 2**31 else np.int64)
    first = np.empty(labels, pos.dtype)
    first[idx[::-1]] = pos[::-1]
    return first


def _first_appearance_ranks(
    idx: np.ndarray, first: np.ndarray
) -> Optional[np.ndarray]:
    """Each element's label ranked by first appearance, or ``None`` where
    some ``first[k]`` is not the first position of ``k`` in ``idx``."""
    n = idx.size
    at = np.empty(n, first.dtype)  # the position ``first`` gives each label
    rank = np.empty(n, np.intp)  # first positions up to each position
    blocks = [slice(s, min(s + _BLOCK, n)) for s in range(0, n, _BLOCK)]

    def count_firsts(b: slice) -> int:
        np.take(first, idx[b], out=at[b])
        pos = np.arange(b.start, b.stop, dtype=at.dtype)
        # each first[k] is a position of k: no later than any of them, it is
        # the first
        if not (at[b] <= pos).all():
            return -1
        np.cumsum(at[b] == pos, out=rank[b])
        return int(rank[b.stop - 1])

    with ThreadPoolExecutor(min(len(blocks), _THREADS)) as pool:
        counts = list(pool.map(count_firsts, blocks))
        if min(counts) < 0:
            return None
        before = np.cumsum([-1] + counts[:-1])  # less one: ranks start at 0
        list(pool.map(lambda b, k: np.add(rank[b], k, out=rank[b]), blocks, before))
        out = np.empty(n, np.intp)
        list(pool.map(lambda b: np.take(rank, at[b], out=out[b]), blocks))
    return out
