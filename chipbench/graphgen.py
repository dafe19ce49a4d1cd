"""Edge streams for the benchmark, made from the seed, and the file users
feed to the clusterer.

The stream is the Graph500 Kronecker edge list, made as the specification's
reference generator (``kronecker_generator.m``) makes it: ``N = 2**scale``
vertices, ``M = edgefactor * N`` edges, each edge drawn independently by
descending ``scale`` levels of the 2x2 initiator ``[[A, B], [C, D]]``, one
bit of ``i`` and one of ``j`` per level, and the vertex labels randomly
permuted.  Self-loops and repeated edges are kept, as the generator makes
them.  The specification also shuffles the edge order; the edges are drawn
independently of each other, so their order is already random and that
shuffle is left out.

The draws run on the device, in blocks of ``BLOCK_ROWS`` edges through one
compiled program, from a key made of the whole seed.

File: little-endian int32 ``(i, j)`` pairs, the raw format that
``BinaryFileSource`` maps.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 22


def key_of(seed: int) -> jax.Array:
    """A key made of all the bits of ``seed`` (``jax.random.key`` keeps the
    low 32 only)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**32), (seed >> 32) % 2**32)


@partial(jax.jit, static_argnames=("scale", "rows", "initiator"))
def _block(key, perm, *, scale: int, rows: int, initiator: tuple):
    a, b, c = initiator[:3]
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)

    def level(k, ij):
        i, j = ij
        u = jax.random.uniform(jax.random.fold_in(key, k), (2, rows))
        i_bit = u[0] > ab
        j_bit = u[1] > jnp.where(i_bit, c_norm, a_norm)
        return i | (i_bit.astype(jnp.int32) << k), j | (j_bit.astype(jnp.int32) << k)

    zero = jnp.zeros(rows, jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return jnp.stack([perm[i], perm[j]], axis=1)


def kronecker_edges(scale: int, edgefactor: int, initiator, seed: int) -> np.ndarray:
    """``(edgefactor * 2**scale, 2)`` int32 edges in arrival order, all ids
    in ``[0, 2**scale)``."""
    n, m = 1 << scale, edgefactor << scale
    initiator = tuple(float(x) for x in initiator)
    k_perm, k_edges = jax.random.split(key_of(seed))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    rows = min(m, BLOCK_ROWS)
    out = np.empty((m, 2), np.int32)
    for blk, start in enumerate(range(0, m, rows)):
        e = _block(jax.random.fold_in(k_edges, blk), perm, scale=scale, rows=rows,
                   initiator=initiator)
        out[start : start + rows] = np.asarray(e)[: m - start]
    return out


def write_binary(path: str, edges: np.ndarray) -> int:
    """Raw int32 pairs, flushed to the disk; returns the bytes written."""
    data = np.ascontiguousarray(edges, dtype="<i4")
    with open(path, "wb") as f:
        f.write(memoryview(data).cast("B"))
        f.flush()
        os.fsync(f.fileno())
    return data.nbytes
