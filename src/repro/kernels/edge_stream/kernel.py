"""Pallas TPU kernels: Algorithm 1 with the full (d, c, v) state in VMEM.

TPU adaptation of the paper's CPU pointer-chasing loop (DESIGN.md §3): the
state is exactly ``3n`` int32 and stays in VMEM for a whole dispatch, so
every per-edge load/store hits VMEM instead of HBM.  Semantics are bit-exact
with ``core.streaming.cluster_stream_dense``: one sequential ``fori_loop``
per edge chunk preserves the paper's strict stream order.

What Mosaic (the TPU kernel compiler in libtpu 0.0.34, JAX 0.9.0) accepts
on a v5e, and therefore the layout of :func:`edge_stream_kernel`:

* **No scalar touches VMEM.**  Mosaic refuses scalar loads and stores on
  VMEM refs ("Cannot store scalars to VMEM").  Each state array is held as
  ``(rows, 128)`` int32 tiles, ``rows = 8 * ceil(n / 1024)`` (the (8, 128)
  tiling rule), and node ``i`` lives at ``(i >> 7, i & 127)``.  A read is
  a one-row load plus a lane-masked sum; a write is a lane-masked
  read-modify-write of that row (:func:`_get`, :func:`_add`, :func:`_set`).
* **Edge ids live in SMEM.**  They are used as scalars (row addresses), so
  edge chunks are DMA'd HBM -> SMEM into two slots, chunk ``t+1`` streaming
  in while chunk ``t``'s loop runs.  A chunk is laid out ``(2 * chunk /
  128, 128)`` with ``(i, j)`` pairs interleaved, so the DMA source slice is
  a whole leading-axis index (Mosaic refuses slices that split a tile).
* **The VMEM limit is explicit.**  The state is DMA'd from HBM into VMEM
  scratch once per dispatch and back once at the end (the HBM buffers are
  aliased in place), taking ``12 * 128 * rows`` bytes.  A v5e has 128 MiB
  of VMEM and the kernel asks for all of it: the compiler accepts
  n = 11,100,000 and refuses n = 11,300,000 ("Ran out of memory in memory
  space vmem"), so :data:`MAX_NODES` covers com-LiveJournal (n = 3,997,962)
  but not com-Friendster.

One kernel serves every bit-exact edge-slab dispatch: the grid runs over
independent streams, so a per-batch call and a ``(K, B, 2)`` megabatch are
one stream (``T = 1``) and the tenant-major fleet is ``T`` streams, each
with its own state rows.  The fused DVE3 decode→update kernel keeps the
same state layout and decodes each edge from payload words DMA'd into SMEM
with scalar shifts and masks (Mosaic has no lowering for the vector
``dynamic_slice`` byte unpacking a lane-parallel decode would need).

The wavefront kernel at the end of this module still keeps a flat 1-D
state and vector gathers over it, which Mosaic refuses ("Only 2D gather is
supported"); it runs in interpret mode off a TPU, and on a TPU the
configuration that reaches it raises the compiler's error (ROADMAP reach
item 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.decode import zigzag_decode32
from repro.core.wavefront import wave_apply, wave_conflict, wave_live
from repro.graph.pipeline import (
    D_BASE,
    D_KIND,
    D_NROWS,
    D_OFF_I,
    D_OFF_J,
    D_W_I,
    D_W_J,
    DESC_COLS,
    DESC_EMPTY,
    DESC_RAW,
    PAD,
)

LANES = 128
SUBLANES = 8
N_EDGE_SLOTS = 2  # double buffering: one slot streams in, one is consumed
VMEM_BYTES = 128 << 20  # v5e VMEM capacity, requested as the scoped limit
MAX_NODES = 11_100_000  # largest n the v5e compiler accepts (see above)
GROUP_BYTES = 4 * SUBLANES * LANES  # payload bytes per (8, 128) int32 group
DECODE_ROWS = 2048  # stream rows decoded per pair of span DMAs
# groups one span DMA covers: 8 bytes per row at most, from any start byte
SPAN_GROUPS = -(-8 * DECODE_ROWS // GROUP_BYTES) + 1


def state_rows(n: int) -> int:
    """Rows of the ``(rows, 128)`` tile layout holding ``n`` node slots."""
    return -(-n // (SUBLANES * LANES)) * SUBLANES


def edge_chunk(chunk: int) -> int:
    """DMA chunk in edges: ``chunk`` rounded up to fill whole 128-lane rows
    of interleaved ``(i, j)`` pairs."""
    return -(-chunk // (LANES // 2)) * (LANES // 2)


def _lane_mask(idx):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return lanes == (idx & (LANES - 1))


def _get(ref, idx):
    """``ref[idx]`` of a tiled state array, without a scalar VMEM load."""
    row = ref[pl.ds(idx >> 7, 1), :]
    return jnp.sum(jnp.where(_lane_mask(idx), row, 0))


def _add(ref, idx, delta):
    """``ref[idx] += delta`` as a lane-masked read-modify-write of one row."""
    rows = pl.ds(idx >> 7, 1)
    ref[rows, :] = ref[rows, :] + jnp.where(_lane_mask(idx), delta, 0)


def _set(ref, idx, value):
    """``ref[idx] = value`` as a lane-masked read-modify-write of one row."""
    rows = pl.ds(idx >> 7, 1)
    ref[rows, :] = jnp.where(_lane_mask(idx), value, ref[rows, :])


def _apply_edge(i, j, d_ref, c_ref, v_ref, *, v_max: int):
    """One Algorithm-1 step against the VMEM-resident tiled (d, c, v)."""

    @pl.when((i != PAD) & (j != PAD) & (i != j))
    def _update():
        _add(d_ref, i, 1)
        _add(d_ref, j, 1)
        di = _get(d_ref, i)
        dj = _get(d_ref, j)
        ci = _get(c_ref, i)
        cj = _get(c_ref, j)
        # Sequential +1 per endpoint community; reload so ci == cj sees +2.
        _add(v_ref, ci, 1)
        _add(v_ref, cj, 1)
        vci = _get(v_ref, ci)
        vcj = _get(v_ref, cj)
        ok = (vci <= v_max) & (vcj <= v_max)

        @pl.when(ok & (vci <= vcj))
        def _move_i():  # i joins the community of j
            _add(v_ref, cj, di)
            _add(v_ref, ci, -di)
            _set(c_ref, i, cj)

        @pl.when(ok & (vci > vcj))
        def _move_j():  # j joins the community of i
            _add(v_ref, ci, dj)
            _add(v_ref, cj, -dj)
            _set(c_ref, j, ci)


def _copy_state(t, state, sems, *, load: bool):
    """DMA stream ``t``'s (d, c, v) from HBM into the VMEM scratch
    (``load``) or back; ``state`` holds ``(hbm_in, vmem, hbm_out)``."""
    copies = [
        pltpu.make_async_copy(src.at[t], vmem, sems.at[s])
        if load
        else pltpu.make_async_copy(vmem, dst.at[t], sems.at[s])
        for s, (src, vmem, dst) in enumerate(state)
    ]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()


def edge_stream_kernel(
    edges_hbm,
    d_in,
    c_in,
    v_in,
    d_out,
    c_out,
    v_out,
    d_ref,
    c_ref,
    v_ref,
    slots_ref,
    edge_sems,
    state_sems,
    *,
    v_max: int,
    chunk: int,
    n_chunks: int,
):
    """Grid step ``t`` ingests stream ``t``'s edge chunks into its state.

    ``edges_hbm`` is ``(T, n_chunks, 2 * chunk / 128, 128)`` in HBM; the
    state arrays are ``(T, rows, 128)`` in HBM, aliased input to output.
    Stream ``t``'s state is DMA'd into the VMEM scratch once, chunk ``k+1``
    of its edges streams into the free SMEM slot while chunk ``k``'s
    sequential loop runs, and the state is DMA'd back once at the end.
    Streams never share state, so the grid order is irrelevant and each
    stream's result is bit-exact with a standalone sequential run; PAD rows
    (a ragged tail, an idle tenant) are no-ops.
    """
    t = pl.program_id(0)
    state = ((d_in, d_ref, d_out), (c_in, c_ref, c_out), (v_in, v_ref, v_out))

    def edge_dma(k):
        slot = jax.lax.rem(k, N_EDGE_SLOTS)
        return pltpu.make_async_copy(
            edges_hbm.at[t, k], slots_ref.at[slot], edge_sems.at[slot]
        )

    edge_dma(jnp.int32(0)).start()
    _copy_state(t, state, state_sems, load=True)

    def chunk_body(k, carry):
        @pl.when(k + 1 < n_chunks)
        def _prefetch_next():
            edge_dma(k + 1).start()

        edge_dma(k).wait()
        slot = jax.lax.rem(k, N_EDGE_SLOTS)

        def body(e, cy):
            p = 2 * e  # flat position of the pair in the (rows, 128) slot
            _apply_edge(
                slots_ref[slot, p >> 7, p & (LANES - 1)],
                slots_ref[slot, p >> 7, (p + 1) & (LANES - 1)],
                d_ref,
                c_ref,
                v_ref,
                v_max=v_max,
            )
            return cy

        jax.lax.fori_loop(0, chunk, body, None)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, None)
    _copy_state(t, state, state_sems, load=False)


def build_call(
    tenants: int,
    n: int,
    chunk: int,
    n_chunks: int,
    v_max: int,
    interpret: bool,
):
    """One dispatch over ``tenants`` independent streams: edges
    ``(T, n_chunks, 2 * chunk / 128, 128)`` and states ``(T, rows, 128)``,
    both left in HBM and DMA'd by the kernel (``chunk`` must be an
    :func:`edge_chunk`)."""
    rows = state_rows(n)
    kernel = functools.partial(
        edge_stream_kernel, v_max=v_max, chunk=chunk, n_chunks=n_chunks
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state = jax.ShapeDtypeStruct((tenants, rows, LANES), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(tenants,),
        in_specs=[hbm] * 4,
        out_specs=[hbm] * 3,
        out_shape=[state] * 3,  # d, c, v
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.SMEM((N_EDGE_SLOTS, 2 * chunk // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((N_EDGE_SLOTS,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )


def payload_groups(payload_bytes: int) -> int:
    """``(8, 128)``-word groups of the padded payload: every span DMA of
    :func:`edge_stream_decode_update_kernel` starts inside the payload and
    stays in bounds."""
    return -(-payload_bytes // GROUP_BYTES) + SPAN_GROUPS


def _span_value(span_ref, pos, width):
    """Little-endian ``width``-byte (1, 2 or 4) value at byte ``pos`` of a
    ``(SPAN_GROUPS, 8, 128)`` int32 SMEM span; segments are 8-byte aligned,
    so a value never straddles two words."""
    q = pos >> 2
    word = span_ref[q >> 10, (q >> 7) & (SUBLANES - 1), q & (LANES - 1)]
    mask = jnp.where(width == 1, 0xFF, jnp.where(width == 2, 0xFFFF, -1))
    return (word >> ((pos & 3) * 8)) & mask


def edge_stream_decode_update_kernel(
    desc_ref,
    payload_hbm,
    d_in,
    c_in,
    v_in,
    d_out,
    c_out,
    v_out,
    live_out,
    d_ref,
    c_ref,
    v_ref,
    spans_ref,
    span_sems,
    state_sems,
    *,
    v_max: int,
    d_max: int,
):
    """Fused decode→update: compressed bytes in, clustered state out.

    One launch per compressed megabatch.  ``desc_ref`` is the flattened
    descriptor table in SMEM; ``payload_hbm`` the payload as ``(G, 8, 128)``
    int32 words left in HBM.  Live descriptors tile the stream in order
    (``CompressedMegaBatch.validate``), so walking them in order and
    decoding each one's rows with scalar arithmetic replays exactly the
    stream the host-decode path stages.  Each descriptor is two byte
    columns — DVE3 zigzag ``i`` deltas and ``j - i`` offsets, or the ``i``
    and ``j`` words of raw int32 pairs — DMA'd into SMEM
    :data:`DECODE_ROWS` rows at a time; row ``e`` is decoded and applied at
    once with :func:`_apply_edge`, so the decoded edges never touch HBM.
    The (d, c, v) tiles are DMA'd into VMEM once and back once, and
    ``live_out[0]`` returns the live-edge count.
    """
    state = ((d_in, d_ref, d_out), (c_in, c_ref, c_out), (v_in, v_ref, v_out))
    _copy_state(0, state, state_sems, load=True)

    def desc_body(t, live):
        row = t * DESC_COLS
        kind = desc_ref[row + D_KIND]
        raw = kind == DESC_RAW
        nrows = jnp.where(kind == DESC_EMPTY, 0, desc_ref[row + D_NROWS])
        off_i = desc_ref[row + D_OFF_I]
        w_i = desc_ref[row + D_W_I]
        w_j = desc_ref[row + D_W_J]
        starts = (off_i, jnp.where(raw, off_i + 4, desc_ref[row + D_OFF_J]))
        strides = (jnp.where(raw, 8, w_i), jnp.where(raw, 8, w_j))
        widths = (jnp.where(raw, 4, w_i), jnp.where(raw, 4, w_j))

        def sub_body(s, carry):
            e0 = s * DECODE_ROWS
            firsts, copies = [], []
            for col in range(2):
                first = starts[col] + e0 * strides[col]
                group = first // GROUP_BYTES
                copies.append(
                    pltpu.make_async_copy(
                        payload_hbm.at[pl.ds(group, SPAN_GROUPS)],
                        spans_ref.at[col],
                        span_sems.at[col],
                    )
                )
                firsts.append(first - group * GROUP_BYTES)
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()

            def edge_body(e, cy):
                acc, live = cy
                a, b = (
                    _span_value(spans_ref.at[col],
                                firsts[col] + e * strides[col], widths[col])
                    for col in range(2)
                )
                i = jnp.where(raw, a, acc + zigzag_decode32(a))
                j = jnp.where(raw, b, i + zigzag_decode32(b))
                _apply_edge(i, j, d_ref, c_ref, v_ref, v_max=v_max)
                ok = (i != PAD) & (j != PAD) & (i != j)
                return i, live + ok.astype(jnp.int32)

            rows = jnp.minimum(DECODE_ROWS, nrows - e0)
            return jax.lax.fori_loop(0, rows, edge_body, carry)

        n_sub = (nrows + DECODE_ROWS - 1) // DECODE_ROWS
        base = desc_ref[row + D_BASE]
        _, live = jax.lax.fori_loop(0, n_sub, sub_body, (base, live))
        return live

    live_out[0] = jax.lax.fori_loop(0, d_max, desc_body, jnp.int32(0))
    _copy_state(0, state, state_sems, load=False)


def build_decode_update_call(
    n: int, groups: int, d_max: int, v_max: int, interpret: bool
):
    """One fused dispatch over a compressed megabatch: the flattened
    ``(d_max * DESC_COLS,)`` descriptor table in SMEM, the ``(groups, 8,
    128)`` payload words and the ``(1, rows, 128)`` states left in HBM
    (states aliased in place); returns d, c, v and a ``(1,)`` live-edge
    count."""
    rows = state_rows(n)
    kernel = functools.partial(
        edge_stream_decode_update_kernel, v_max=v_max, d_max=d_max
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state = jax.ShapeDtypeStruct((1, rows, LANES), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[smem] + [hbm] * 4,
        out_specs=[hbm] * 3 + [smem],
        out_shape=[state] * 3 + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.SMEM((2, SPAN_GROUPS, SUBLANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Flat-layout kernel: interpret mode only (Mosaic refuses it, see above)
# ---------------------------------------------------------------------------

def _apply_edge_flat(i_raw, j_raw, d_ref, c_ref, v_ref, *, v_max: int):
    """One Algorithm-1 step against flat 1-D (d, c, v) refs, by scalar
    loads and stores — the form Mosaic refuses on VMEM."""
    live = (i_raw != PAD) & (j_raw != PAD) & (i_raw != j_raw)
    i = jnp.maximum(i_raw, 0)
    j = jnp.maximum(j_raw, 0)

    @pl.when(live)
    def _update():
        di = d_ref[i] + 1
        d_ref[i] = di
        dj = d_ref[j] + 1
        d_ref[j] = dj

        ci = c_ref[i]
        cj = c_ref[j]
        v_ref[ci] = v_ref[ci] + 1
        v_ref[cj] = v_ref[cj] + 1
        vci = v_ref[ci]
        vcj = v_ref[cj]

        ok = (vci <= v_max) & (vcj <= v_max)

        @pl.when(ok & (vci <= vcj))
        def _move_i():
            v_ref[cj] = v_ref[cj] + di
            v_ref[ci] = v_ref[ci] - di
            c_ref[i] = cj

        @pl.when(ok & (vci > vcj))
        def _move_j():
            v_ref[ci] = v_ref[ci] + dj
            v_ref[cj] = v_ref[cj] - dj
            c_ref[j] = ci


def edge_stream_wavefront_kernel(
    waves_hbm_ref,
    left_hbm_ref,
    meta_ref,
    d0_ref,
    c0_ref,
    v0_ref,
    d_ref,
    c_ref,
    v_ref,
    stats_ref,
    *,
    v_max: int,
    n: int,
    width: int,
    n_waves: int,
    chunk: int,
    n_left_chunks: int,
):
    """Wave-vectorised megabatch kernel (DESIGN.md §12).

    ``waves_hbm_ref`` holds the planner's ``(n_waves, width, 2)`` layout in
    HBM; waves are double-buffer DMA'd into VMEM like the sequential
    kernel's edge chunks.  Each wave is applied as gathered vector
    loads / scattered stores against the VMEM-resident (d, c, v) via the
    shared :func:`repro.core.wavefront.wave_apply` — after a runtime
    community-disjointness check (:func:`wave_conflict`) against the live
    state; colliding waves fall back to the sequential per-edge
    ``fori_loop``, so labels stay bit-identical to
    :func:`edge_stream_kernel` for any plan.  The uncovered
    stream suffix (``meta_ref[1]`` rows, chunked in ``left_hbm_ref``) is
    drained sequentially at the end.  ``meta_ref[0]`` bounds the wave loop
    so trailing all-PAD waves cost nothing.  ``stats_ref`` returns
    ``[live_waves, fallback_waves]``.
    """
    d_ref[...] = d0_ref[...]
    c_ref[...] = c0_ref[...]
    v_ref[...] = v0_ref[...]
    stats_ref[...] = jnp.zeros((2,), jnp.int32)
    nw = jnp.minimum(meta_ref[0], n_waves)
    left_rows = meta_ref[1]

    def waves_scoped(slots_ref, sems_ref):
        def wave_dma(t):
            slot = jax.lax.rem(t, N_EDGE_SLOTS)
            return pltpu.make_async_copy(
                waves_hbm_ref.at[t], slots_ref.at[slot], sems_ref.at[slot]
            )

        @pl.when(nw > 0)
        def _warmup():
            wave_dma(jnp.int32(0)).start()

        def wave_body(t, carry):
            @pl.when(t + 1 < nw)
            def _prefetch_next():
                wave_dma(t + 1).start()

            wave_dma(t).wait()
            slot = jax.lax.rem(t, N_EDGE_SLOTS)
            wave = slots_ref[slot]
            i_raw = wave[:, 0]
            j_raw = wave[:, 1]
            c_now = c_ref[...]
            v_now = v_ref[...]
            has_live = jnp.any(wave_live(i_raw, j_raw))
            conflict = wave_conflict(c_now, v_now, i_raw, j_raw, v_max, n)

            @pl.when(jnp.logical_not(conflict))
            def _vector():
                d2, c2, v2 = wave_apply(
                    d_ref[...], c_now, v_now, i_raw, j_raw, v_max
                )
                d_ref[...] = d2
                c_ref[...] = c2
                v_ref[...] = v2

            @pl.when(conflict)
            def _sequential():
                def body(e, cy):
                    _apply_edge_flat(
                        wave[e, 0], wave[e, 1], d_ref, c_ref, v_ref,
                        v_max=v_max,
                    )
                    return cy

                jax.lax.fori_loop(0, width, body, None)

            stats_ref[0] = stats_ref[0] + has_live.astype(jnp.int32)
            stats_ref[1] = stats_ref[1] + (conflict & has_live).astype(
                jnp.int32
            )
            return carry

        jax.lax.fori_loop(0, nw, wave_body, None)

    pl.run_scoped(
        waves_scoped,
        pltpu.VMEM((N_EDGE_SLOTS, width, 2), jnp.int32),
        pltpu.SemaphoreType.DMA((N_EDGE_SLOTS,)),
    )

    # leftover suffix: strictly sequential, single-buffered (rare path —
    # non-empty only when the planner's wave budget ran out)
    n_live_chunks = jnp.minimum(
        (left_rows + chunk - 1) // chunk, n_left_chunks
    )

    def left_scoped(slot_ref, sem_ref):
        def chunk_body(t, carry):
            cp = pltpu.make_async_copy(left_hbm_ref.at[t], slot_ref, sem_ref)
            cp.start()
            cp.wait()

            def body(e, cy):
                _apply_edge_flat(
                    slot_ref[e, 0], slot_ref[e, 1], d_ref, c_ref, v_ref,
                    v_max=v_max,
                )
                return cy

            jax.lax.fori_loop(0, chunk, body, None)
            return carry

        jax.lax.fori_loop(0, n_live_chunks, chunk_body, None)

    pl.run_scoped(
        left_scoped,
        pltpu.VMEM((chunk, 2), jnp.int32),
        pltpu.SemaphoreType.DMA(()),
    )


def build_wavefront_call(
    n: int,
    width: int,
    n_waves: int,
    chunk: int,
    n_left_chunks: int,
    v_max: int,
    interpret: bool,
):
    """One fused dispatch over a planned megabatch: waves and the leftover
    suffix stay in HBM and are DMA'd by the kernel; the 3n-int state is
    seeded into VMEM once and written back once, plus a ``(2,)`` stats
    output ``[live_waves, fallback_waves]``."""
    kernel = functools.partial(
        edge_stream_wavefront_kernel,
        v_max=v_max,
        n=n,
        width=width,
        n_waves=n_waves,
        chunk=chunk,
        n_left_chunks=n_left_chunks,
    )
    state_spec = pl.BlockSpec((n,), lambda: (0,))
    stats_spec = pl.BlockSpec((2,), lambda: (0,))
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            stats_spec,
            state_spec,
            state_spec,
            state_spec,
        ],
        out_specs=[state_spec, state_spec, state_spec, stats_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),  # d
            jax.ShapeDtypeStruct((n,), jnp.int32),  # c
            jax.ShapeDtypeStruct((n,), jnp.int32),  # v
            jax.ShapeDtypeStruct((2,), jnp.int32),  # stats
        ],
        interpret=interpret,
    )
