"""Pure-JAX reference decoder for compressed megabatch slabs (DESIGN.md §14).

The compressed ingest path (:meth:`BatchPipeline.compressed_megabatches`)
ships DVE3 payload bytes plus a descriptor table instead of decoded edges;
this module is the *specification* of what decoding that slab means:

* the device-decode test suite pins it to the host-staged slab, and the
  fused Pallas decode→update kernel to host decode + the sequential kernel;
* :func:`chunked_decode_update_megabatch` fuses decode + the Jacobi
  megabatch update under one jit so the chunked tier keeps its
  one-dispatch-per-megabatch contract with ``device_decode`` on.

Decoded output is defined to equal the ``(K * B, 2)`` PAD-carved slab the
host-decode staging path would have produced for the same rows — that
identity (not merely label equality) is what makes cursors and labels
interchangeable between ``device_decode`` on and off.

All arithmetic is int32: the DVE3 encoder only emits device-decodable
(``DESC_FIXED``) blocks when every zigzag value fits 31 bits, so the
shift/xor/cumsum chain below is exact; wider blocks arrive host-decoded as
``DESC_RAW`` int32 rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.chunked import chunked_update_megabatch
from repro.core.state import ClusterState
from repro.graph.pipeline import (
    D_BASE,
    D_KIND,
    D_NROWS,
    D_OFF_I,
    D_OFF_J,
    D_ROW,
    D_W_I,
    D_W_J,
    DESC_EMPTY,
    DESC_RAW,
    PAD,
)


def zigzag_decode32(z):
    """Inverse zigzag on int32 (exact: fixed lanes are capped below 2**31)."""
    return (z >> 1) ^ -(z & 1)


def _lane_view(pay, nbytes):
    """Reinterpret the (padded) payload as little-endian ``nbytes``-wide
    lanes.  Segment offsets are ``_SEGMENT_ALIGN``-aligned, so a width-w
    column always starts on a w-aligned boundary and one gather per lane
    replaces the per-byte combine."""
    if nbytes == 1:
        return pay
    return jax.lax.bitcast_convert_type(
        pay.reshape(-1, nbytes), jnp.uint16 if nbytes == 2 else jnp.uint32
    )


def _gather_w(view, off, nbytes, window):
    """Gather (D, window) int32 lanes of width ``nbytes`` from the matching
    :func:`_lane_view`; ``off`` is in bytes.  Out-of-range indices clamp
    (their lanes are masked out downstream)."""
    k = jnp.arange(window, dtype=jnp.int32)
    idx = (off[:, None] // nbytes) + k[None, :]
    v = jnp.take(view, idx, mode="clip")
    if nbytes == 4:
        return jax.lax.bitcast_convert_type(v, jnp.int32)
    return v.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "out_rows"))
def decode_megabatch(payload, desc, window: int, out_rows: int):
    """Decode a compressed slab to its ``(out_rows, 2)`` int32 edge slab.

    ``payload`` is the ``(P,)`` uint8 staging buffer, ``desc`` the
    ``(D, DESC_COLS)`` int32 descriptor table (:mod:`repro.graph.pipeline`
    layout).  Every descriptor decodes as one ``window``-row lane batch —
    fixed lanes are gathered per candidate width and selected, so the whole
    table decodes in a handful of vector passes with no host loop.  Rows
    past a descriptor's ``n_rows`` and rows no descriptor covers come out
    PAD, reproducing the host-staged slab exactly.
    """
    kind = desc[:, D_KIND]
    dest = desc[:, D_ROW]
    nrows = desc[:, D_NROWS]
    off_i, off_j = desc[:, D_OFF_I], desc[:, D_OFF_J]
    w_i, w_j = desc[:, D_W_I], desc[:, D_W_J]
    base = desc[:, D_BASE]

    view2 = _lane_view(payload, 2)
    view4 = _lane_view(payload, 4)

    def fixed_col(off, w):
        v1 = _gather_w(payload, off, 1, window)
        v2 = _gather_w(view2, off, 2, window)
        v4 = _gather_w(view4, off, 4, window)
        return jnp.where(
            w[:, None] == 1, v1, jnp.where(w[:, None] == 2, v2, v4)
        )

    di = zigzag_decode32(fixed_col(off_i, w_i))
    fixed_i = base[:, None] + jnp.cumsum(di, axis=1, dtype=jnp.int32)
    fixed_j = fixed_i + zigzag_decode32(fixed_col(off_j, w_j))

    # DESC_RAW: (n, 2) little-endian int32 pairs at off_i — 8-byte stride
    k = jnp.arange(window, dtype=jnp.int32)
    raw_idx = (off_i[:, None] // 4) + 2 * k[None, :]
    raw_i = jax.lax.bitcast_convert_type(
        jnp.take(view4, raw_idx, mode="clip"), jnp.int32
    )
    raw_j = jax.lax.bitcast_convert_type(
        jnp.take(view4, raw_idx + 1, mode="clip"), jnp.int32
    )

    raw = (kind == DESC_RAW)[:, None]
    vals_i = jnp.where(raw, raw_i, fixed_i)
    vals_j = jnp.where(raw, raw_j, fixed_j)

    # output-stationary assembly: each output row looks up its covering
    # descriptor (live descriptors tile the row space in ascending order;
    # dead table rows sort past the end) and gathers its lane — no scatter
    r = jnp.arange(out_rows, dtype=jnp.int32)
    dest_eff = jnp.where(kind == DESC_EMPTY, out_rows, dest)
    d = jnp.searchsorted(dest_eff, r, side="right").astype(jnp.int32) - 1
    d = jnp.clip(d, 0, desc.shape[0] - 1)
    lane = r - dest_eff[d]
    ok = (lane >= 0) & (lane < nrows[d]) & (kind[d] != DESC_EMPTY)
    flat = jnp.clip(d * window + lane, 0, None)
    out_i = jnp.where(ok, jnp.take(vals_i.reshape(-1), flat, mode="clip"), PAD)
    out_j = jnp.where(ok, jnp.take(vals_j.reshape(-1), flat, mode="clip"), PAD)
    return jnp.stack([out_i, out_j], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "window", "out_rows", "chunk"),
    donate_argnums=(0,),
)
def chunked_decode_update_megabatch(
    state: ClusterState,
    payload,
    desc,
    v_max: int,
    window: int,
    out_rows: int,
    chunk: int,
) -> ClusterState:
    """Decode a compressed slab and run the fused Jacobi megabatch update —
    one jit, one dispatch, exactly the slab the host-decode path would have
    fed ``chunked_update_megabatch`` (so labels are bit-identical to
    ``device_decode=False`` on the chunked tier)."""
    edges = decode_megabatch(payload, desc, window, out_rows)
    return chunked_update_megabatch(
        state, edges.reshape(1, out_rows, 2), jnp.int32(v_max), chunk=chunk
    )
