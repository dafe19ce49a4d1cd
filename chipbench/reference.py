"""Plain references for the clusterer's two update rules.

Nothing here imports the program.  Both rules keep the paper's state: a
degree ``d``, a community ``c`` (initially the node's own id) and a
community volume ``v`` per node.  An edge ``(i, j)`` with ``i != j`` and
no negative id is live; every other row is skipped.

* :func:`sequential` is the paper's Algorithm 1, one edge at a time: both
  degrees and both community volumes go up by one; if both volumes are at
  most ``v_max``, the endpoint whose community volume is smaller (``i`` on
  a tie) moves to the other's community and carries its degree along.
* :func:`jacobi` applies the same rule to consecutive chunks of ``chunk``
  rows, all read from the state before the chunk: degrees and volumes of
  the whole chunk are added first, decisions are taken on those volumes
  and the communities from before the chunk, and where several edges would
  move one node, the first of them in stream order moves it.

Each returns the community array at each requested stop row, so one pass
over a stream checks every prefix the benchmark needs.  Both rules run in C
(``reference.c``, built with the system's compiler once per process), where
Python takes about a microsecond per edge and numpy about 200 per 1,024-edge
chunk; where no compiler exists the same rules run in Python
(:func:`sequential_py`, :func:`jacobi_py`).
"""

from __future__ import annotations

import ctypes
import functools
import os
import shutil
import subprocess
from typing import Dict, Iterable

import numpy as np

BLOCK_ROWS = 1 << 20  # rows converted to Python lists at a time
HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE_C = os.path.join(HERE, "reference.c")
BUILD_DIR = os.path.join(os.path.dirname(HERE), ".chipbench_data", "build")


def canonical(c: np.ndarray) -> np.ndarray:
    """Community ids renumbered 0, 1, ... in order of first appearance."""
    _, first, inv = np.unique(np.asarray(c), return_index=True, return_inverse=True)
    rank = np.empty(first.size, np.int64)
    rank[np.argsort(first, kind="stable")] = np.arange(first.size)
    return rank[inv.reshape(-1)]


def live_count(edges: np.ndarray) -> int:
    i, j = edges[:, 0], edges[:, 1]
    return int(np.count_nonzero((i >= 0) & (j >= 0) & (i != j)))


@functools.lru_cache(maxsize=None)
def _library():
    """``reference.c`` built with the system's C compiler and loaded, or
    ``None`` where there is no compiler."""
    cc = shutil.which("cc") or shutil.which("gcc")
    if cc is None:
        return None
    os.makedirs(BUILD_DIR, exist_ok=True)
    path = os.path.join(BUILD_DIR, f"reference-{os.getpid()}.so")
    subprocess.run([cc, "-O2", "-shared", "-fPIC", "-o", path, REFERENCE_C], check=True)
    try:
        lib = ctypes.CDLL(path)
    finally:
        os.remove(path)  # the loaded library stays mapped
    i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.sequential.argtypes = [i32, ctypes.c_int64, i32, i32, i32, ctypes.c_int32]
    lib.sequential.restype = None
    lib.jacobi.argtypes = [i32, ctypes.c_int64, ctypes.c_int32, i32, i32, i32,
                           ctypes.c_int32, i32, i32]
    lib.jacobi.restype = None
    return lib


def _check_stops(edges: np.ndarray, stops: Iterable[int]) -> list:
    stops = sorted(set(int(s) for s in stops))
    if stops and stops[-1] > edges.shape[0]:
        raise ValueError(f"stop {stops[-1]} lies past the stream's end")
    return stops


def sequential(
    edges: np.ndarray, n: int, v_max: int, stops: Iterable[int]
) -> Dict[int, np.ndarray]:
    """Algorithm 1 over ``edges``; ``{stop: c after the first stop rows}``.
    Runs ``reference.c`` where a C compiler exists, else
    :func:`sequential_py`."""
    stops = _check_stops(edges, stops)
    lib = _library()
    if lib is None:
        return sequential_py(edges, n, v_max, stops)
    edges = np.ascontiguousarray(edges, dtype=np.int32)
    d = np.zeros(n, np.int32)
    c = np.arange(n, dtype=np.int32)
    v = np.zeros(n, np.int32)
    out: Dict[int, np.ndarray] = {}
    row = 0
    for stop in stops:
        lib.sequential(edges[row:stop], stop - row, d, c, v, v_max)
        row = stop
        out[stop] = c.astype(np.int64)
    return out


def sequential_py(
    edges: np.ndarray, n: int, v_max: int, stops: Iterable[int]
) -> Dict[int, np.ndarray]:
    """Algorithm 1 in Python lists, one edge at a time."""
    stops = _check_stops(edges, stops)
    d = [0] * n
    c = list(range(n))
    v = [0] * n
    out: Dict[int, np.ndarray] = {}
    row = 0
    for stop in stops:
        while row < stop:
            end = min(stop, row + BLOCK_ROWS)
            block = edges[row:end]
            for i, j in zip(block[:, 0].tolist(), block[:, 1].tolist()):
                if i == j or i < 0 or j < 0:
                    continue
                d[i] += 1
                d[j] += 1
                ci = c[i]
                cj = c[j]
                v[ci] += 1
                v[cj] += 1
                vi = v[ci]
                vj = v[cj]
                if vi <= v_max and vj <= v_max:
                    if vi <= vj:  # i joins the community of j
                        v[cj] += d[i]
                        v[ci] -= d[i]
                        c[i] = cj
                    else:  # j joins the community of i
                        v[ci] += d[j]
                        v[cj] -= d[j]
                        c[j] = ci
            row = end
        out[stop] = np.array(c, np.int64)
    return out


def _jacobi_chunk(d, c, v, i, j, v_max: int) -> None:
    """One chunk of live edges ``(i, j)``, in stream order, in place."""
    np.add.at(d, i, 1)
    np.add.at(d, j, 1)
    ci = c[i]
    cj = c[j]
    np.add.at(v, ci, 1)
    np.add.at(v, cj, 1)
    vi = v[ci]
    vj = v[cj]
    ok = (vi <= v_max) & (vj <= v_max)
    i_joins = ok & (vi <= vj)
    moves = ok  # an ok edge moves i (i_joins) or j (the rest)
    mover = np.where(i_joins, i, j)[moves]
    target = np.where(i_joins, cj, ci)[moves]
    source = np.where(i_joins, ci, cj)[moves]
    # the first edge in stream order that would move a node moves it
    _, first = np.unique(mover, return_index=True)
    mover, target, source = mover[first], target[first], source[first]
    dm = d[mover]
    np.add.at(v, target, dm)
    np.subtract.at(v, source, dm)
    c[mover] = target


def jacobi(
    edges: np.ndarray, n: int, v_max: int, chunk: int, stops: Iterable[int]
) -> Dict[int, np.ndarray]:
    """The chunked Jacobi rule over ``edges``, chunks starting at row 0;
    ``{stop: c after the first stop rows}``.  A stream that ends inside a
    chunk ends with that chunk cut short.  Runs ``reference.c`` where a C
    compiler exists, else :func:`jacobi_py`."""
    stops = _check_stops(edges, stops)
    lib = _library()
    if lib is None:
        return jacobi_py(edges, n, v_max, chunk, stops)
    edges = np.ascontiguousarray(edges, dtype=np.int32)
    d = np.zeros(n, np.int32)
    c = np.arange(n, dtype=np.int32)
    v = np.zeros(n, np.int32)
    moved = np.zeros(n, np.int32)
    buf = np.empty(3 * chunk, np.int32)
    out: Dict[int, np.ndarray] = {}
    row = 0
    for stop in stops:
        whole = stop - stop % chunk
        lib.jacobi(edges[row:whole], whole - row, chunk, d, c, v, v_max, moved, buf)
        row = whole
        if stop == whole:
            out[stop] = c.astype(np.int64)
        else:  # the stream ends inside this chunk
            d2, c2, v2 = d.copy(), c.copy(), v.copy()
            lib.jacobi(edges[whole:stop], stop - whole, chunk, d2, c2, v2, v_max, moved, buf)
            out[stop] = c2.astype(np.int64)
    return out


def jacobi_py(
    edges: np.ndarray, n: int, v_max: int, chunk: int, stops: Iterable[int]
) -> Dict[int, np.ndarray]:
    """The chunked Jacobi rule in numpy, one chunk at a time."""
    stops = _check_stops(edges, stops)
    d = np.zeros(n, np.int64)
    c = np.arange(n, dtype=np.int64)
    v = np.zeros(n, np.int64)

    def apply(d, c, v, rows):
        i, j = rows[:, 0].astype(np.int64), rows[:, 1].astype(np.int64)
        live = (i >= 0) & (j >= 0) & (i != j)
        if not live.all():
            i, j = i[live], j[live]
        if i.size:
            _jacobi_chunk(d, c, v, i, j, v_max)

    out: Dict[int, np.ndarray] = {}
    row = 0
    for stop in stops:
        whole = stop - stop % chunk
        while row < whole:
            apply(d, c, v, edges[row : row + chunk])
            row += chunk
        if stop == whole:
            out[stop] = c.copy()
        else:  # the stream ends inside this chunk
            d2, c2, v2 = d.copy(), c.copy(), v.copy()
            apply(d2, c2, v2, edges[whole:stop])
            out[stop] = c2
    return out
