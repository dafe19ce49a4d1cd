"""Batching/padding pipeline between an edge source and the device tiers.

This module is the single home of the stream-shape plumbing (DESIGN.md
§"Ingestion"):

* :data:`PAD` — the sentinel node id padding fixed device shapes (a PAD edge
  is a no-op in every clustering tier).
* :func:`pad_batch` / :func:`pad_to_chunks` (host, numpy) and
  :func:`pad_edges_to_chunks` (device, jit-traceable) — previously duplicated
  between ``graph/stream.py`` and ``core/streaming.py``; both old names
  remain as shims over these.
* :class:`BatchPipeline` — pulls raw slices from an
  :class:`repro.graph.sources.EdgeSource`, re-chunks them into *fixed-size*
  batches (so every jitted tier compiles exactly once per run), pads with
  PAD, and double-buffers production on a background thread so host parsing
  /generation — *and codec block decompression*: the source's
  ``resume``/``iter_slices`` generators, where
  :class:`~repro.graph.codecs.DeltaVarintCodec` decoding happens, are pulled
  entirely on the prefetch worker — overlaps device compute.  Peak host
  edge-buffer residency is tracked (``peak_buffer_bytes``) — the paper's
  memory claim is state = ``3n`` ints; the pipeline keeps edges at O(batch),
  not O(m).
* :meth:`BatchPipeline.megabatches` — the device-pipelining staging mode
  (DESIGN.md §10): ``K`` consecutive fixed-shape batches are stacked into
  one ``(K, B, 2)`` host buffer on the prefetch thread, so a fused backend
  (``lax.scan``-over-chunks, double-buffered-DMA Pallas) dispatches *once*
  per ``K`` batches instead of once per batch.  A ragged tail megabatch is
  padded with all-PAD batches (no-ops on every tier), keeping the device
  shape constant — one compile per run, bit-identical labels to the
  per-batch path.

Stream positions are :class:`~repro.graph.codecs.Cursor` values;
``batches(start=...)`` accepts either a cursor or the historical raw-row
int.
"""

from __future__ import annotations

import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FuturesTimeout
from typing import Iterable, Iterator, NamedTuple, Optional, Union

import numpy as np

from repro.graph.codecs import Cursor, as_cursor
from repro.graph.errors import RetryPolicy, StallError, retrying_slices
from repro.obs import span

# Sentinel node id used to pad edge batches/chunks to fixed shapes; padded
# edges are no-ops in every clustering tier.  (Canonical definition — re-
# exported by ``repro.core.streaming`` and ``repro.graph.stream`` for
# backwards compatibility.)
PAD = -1

_END = object()  # the end of an iterator, as ``next``'s default


# ---------------------------------------------------------------------------
# Padding primitives (host + device)
# ---------------------------------------------------------------------------

# Preallocated all-PAD row template backing pad_batch / megabatch staging:
# padded buffers are carved by copying template rows instead of a fresh
# ``np.full`` fill per batch.  Grown geometrically under a lock (reads of an
# already-large-enough template are lock-free); ``_pad_template_allocs``
# counts the growths so the smoke bench can assert the steady state
# allocates nothing new.
_pad_template = np.full((1 << 10, 2), PAD, dtype=np.int32)
_pad_template_lock = threading.Lock()
_pad_template_allocs = 1


_pad_template.flags.writeable = False  # a stray write would poison all pads


def pad_template(rows: int) -> np.ndarray:
    """A read-only view of ``rows`` all-PAD ``(rows, 2)`` int32 rows."""
    global _pad_template, _pad_template_allocs
    tmpl = _pad_template
    if tmpl.shape[0] < rows:
        with _pad_template_lock:
            if _pad_template.shape[0] < rows:
                size = max(rows, 2 * _pad_template.shape[0])
                grown = np.full((size, 2), PAD, dtype=np.int32)
                grown.flags.writeable = False
                _pad_template = grown
                _pad_template_allocs += 1
            tmpl = _pad_template
    return tmpl[:rows]


def pad_template_allocs() -> int:
    """How many times the shared PAD template has been (re)allocated —
    a growth counter, not a per-batch one; the smoke bench asserts it stays
    flat across steady-state streaming."""
    return _pad_template_allocs


def pad_batch(edges: np.ndarray, length: int) -> np.ndarray:
    """Pad a host ``(m, 2)`` batch with PAD rows up to exactly ``length``.

    Zero-copy when the batch is already full-length int32 (the steady-state
    case: every non-final pipeline batch); the padded tail is filled from
    the preallocated PAD template rather than a fresh ``np.full``.
    """
    edges = np.asarray(edges)
    m = edges.shape[0]
    if m > length:
        raise ValueError(f"batch of {m} rows exceeds pad length {length}")
    if m == length and edges.dtype == np.int32:
        return edges
    out = np.empty((length, 2), dtype=np.int32)
    out[:m] = edges
    out[m:] = pad_template(length - m)
    return out


def pad_to_chunks(edges: np.ndarray, chunk: int) -> np.ndarray:
    """(m, 2) -> (ceil(m/chunk), chunk, 2), padded with PAD edges (host).

    Always a fresh array (historical contract) — callers may mutate the
    result without aliasing their input; the pipeline's zero-copy fast path
    lives in :func:`pad_batch` instead.
    """
    edges = np.asarray(edges)
    m = edges.shape[0]
    n_chunks = max(1, -(-m // chunk))
    out = np.empty((n_chunks * chunk, 2), dtype=np.int32)
    out[:m] = edges
    out[m:] = pad_template(n_chunks * chunk - m)
    return out.reshape(n_chunks, chunk, 2)


def pad_edges_to_chunks(edges, chunk: int):
    """Pad a (m, 2) *device* batch with PAD rows up to a ``chunk`` multiple.

    Jit-traceable (shapes depend only on ``edges.shape`` and ``chunk``) —
    the DMA/Jacobi granularity of the chunked and Pallas tiers.  Returns
    ``(padded, n_chunks)`` with ``padded`` of shape ``(n_chunks * chunk, 2)``;
    empty batches yield one all-PAD chunk.
    """
    import jax
    import jax.numpy as jnp

    m = edges.shape[0]
    n_chunks = max(1, -(-m // chunk))
    padded = jnp.full((n_chunks * chunk, 2), PAD, dtype=jnp.int32)
    padded = jax.lax.dynamic_update_slice(padded, edges.astype(jnp.int32), (0, 0))
    return padded, n_chunks


def round_up(value: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``value``."""
    return -(-value // multiple) * multiple


# ---------------------------------------------------------------------------
# Re-chunking: arbitrary-size raw slices -> exact-size batches
# ---------------------------------------------------------------------------

def rechunk(slices: Iterable[np.ndarray], size: int) -> Iterator[np.ndarray]:
    """Regroup arbitrary-length ``(k, 2)`` slices into exact ``size``-row
    batches (final batch may be short).

    The batch boundaries depend only on ``size`` — never on how the source
    happened to slice the stream — which is what makes labels invariant
    across sources for a fixed batch size.  Full batches carved out of a
    single large slice are views (zero-copy; mmap'd sources never touch the
    heap for them).
    """
    pending: list = []
    have = 0
    for sl in slices:
        sl = np.asarray(sl)
        if sl.size == 0:
            continue
        pos = 0
        if have:
            take = min(size - have, sl.shape[0])
            pending.append(sl[:take])
            have += take
            pos = take
            if have == size:
                yield np.concatenate(pending).astype(np.int32, copy=False)
                pending, have = [], 0
        while sl.shape[0] - pos >= size:
            yield sl[pos : pos + size]
            pos += size
        if pos < sl.shape[0]:
            pending.append(sl[pos:])
            have = sl.shape[0] - pos
    if have:
        yield np.concatenate(pending).astype(np.int32, copy=False)


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------

class Batch(NamedTuple):
    """One pipeline batch: fixed-shape padded edges + raw-stream bookkeeping."""

    edges: np.ndarray  # (batch_edges, 2) int32, PAD tail
    n_rows: int  # raw source rows in this batch (before PAD padding)
    offset: int  # raw rows consumed from the source before this batch


# ---------------------------------------------------------------------------
# Compressed-slab descriptor table (DESIGN.md §14)
# ---------------------------------------------------------------------------
# One descriptor row per staged segment of a compressed megabatch.  The
# decoder (Pallas kernel or pure-JAX reference) walks rows in order; dest
# windows may overlap the previous segment's PAD tail, so ascending-order
# full-window writes reconstruct exactly the slab the host-decode path
# stages.  Kinds:
#   DESC_EMPTY — unused row (table is fixed-shape), a no-op
#   DESC_FIXED — lane-packed DVE3 block: zigzag cols at off_i/off_j with
#                byte widths w_i/w_j, source column cumsum seeded by base
#   DESC_RAW   — host-decoded int32 (n, 2) rows at off_i (fallback blocks,
#                partial blocks at resume/megabatch boundaries)
DESC_COLS = 8
DESC_EMPTY, DESC_FIXED, DESC_RAW = 0, 1, 2
(
    D_KIND,
    D_ROW,
    D_NROWS,
    D_OFF_I,
    D_OFF_J,
    D_W_I,
    D_W_J,
    D_BASE,
) = range(DESC_COLS)

# Payload slab capacity per staged row.  Fixed lanes cost w_i + w_j <= 8
# bytes/row and raw fallback rows cost exactly 8, so 8 bytes/row (plus the
# per-segment alignment slack added in the producer) can never overflow —
# the compressed path trades *host decode compute* and disk bandwidth, not
# slab bytes, and never needs a mid-stream shape change.
_PAYLOAD_BYTES_PER_ROW = 8
_SEGMENT_ALIGN = 8  # every lane/raw segment starts 8-byte aligned


class MegaBatch(NamedTuple):
    """``K`` stacked pipeline batches staged as one fixed-shape host buffer.

    The fused device paths (``lax.scan``-over-chunks, double-buffered-DMA
    Pallas) consume one of these per dispatch.  ``edges`` always has the
    full ``(K, batch_edges, 2)`` shape — a ragged tail (fewer than ``K``
    real batches left in the stream) is padded with all-PAD batches, which
    are no-ops in every tier, so the device sees exactly one shape per run.
    """

    edges: np.ndarray  # (K, batch_edges, 2) int32, PAD-padded
    n_rows: int  # raw source rows across the megabatch (before padding)
    offset: int  # raw rows consumed from the source before this megabatch
    n_batches: int  # real (non-padding) batches stacked (1..K)
    plan: Optional[object] = None  # WavePlan staged on the prefetch thread
    #   when megabatches(..., wavefront=W) is used (DESIGN.md §12); None in
    #   sequential megabatch mode


class CompressedMegaBatch(NamedTuple):
    """``K`` batches' worth of stream staged as *compressed bytes* plus a
    descriptor table, instead of decoded edges (DESIGN.md §14).

    Decoding the slab (device kernel or pure-JAX reference) must
    reconstruct exactly the ``(K, batch_edges, 2)`` PAD-carved buffer the
    plain :class:`MegaBatch` producer would have staged for the same rows
    — that invariant is what keeps labels bit-identical and cursors
    interchangeable between ``device_decode`` on and off.
    """

    payload: np.ndarray  # (P,) uint8 — lane segments + raw fallback rows
    desc: np.ndarray  # (D_max, DESC_COLS) int32 descriptor table
    n_rows: int  # raw source rows staged (before PAD padding)
    offset: int  # raw rows consumed from the source before this megabatch
    n_batches: int  # real (non-padding) batches covered (1..K)
    n_desc: int  # live descriptor rows (the rest are DESC_EMPTY)
    window: int  # max rows any one descriptor covers (static per run)
    fallback_rows: int  # rows staged as DESC_RAW (host-decoded)
    out_rows: int  # decoded slab rows = k * batch_edges (static per run)

    def validate(self) -> "CompressedMegaBatch":
        """Reject a torn descriptor table before it reaches a decode
        dispatch.  Live descriptors must tile ``[0, n_rows)`` contiguously
        in order, stay inside the payload, and carry device-decodable
        widths — anything else means the slab was corrupted in transit
        (truncated payload, spliced table, bad checkpoint) and decoding it
        would silently produce wrong edges rather than fail.  Returns
        ``self`` so call sites can chain.  O(n_desc), host-side.
        """
        desc = np.asarray(self.desc)
        if not (0 <= self.n_desc <= desc.shape[0]):
            raise ValueError(
                f"torn descriptor table: n_desc {self.n_desc} outside "
                f"table of {desc.shape[0]} rows"
            )
        live, tail = desc[: self.n_desc], desc[self.n_desc :]
        if tail.size and (tail[:, D_KIND] != DESC_EMPTY).any():
            raise ValueError(
                "torn descriptor table: live descriptor past n_desc"
            )
        kind, nrows = live[:, D_KIND], live[:, D_NROWS]
        if not np.isin(kind, (DESC_FIXED, DESC_RAW)).all():
            raise ValueError(
                "torn descriptor table: unknown descriptor kind"
            )
        if ((nrows < 1) | (nrows > self.window)).any():
            raise ValueError(
                "torn descriptor table: segment rows outside (0, window]"
            )
        expect = np.concatenate(([0], np.cumsum(nrows[:-1], dtype=np.int64)))
        if (live[:, D_ROW].astype(np.int64) != expect).any() or (
            int(nrows.sum()) != self.n_rows
        ):
            raise ValueError(
                "torn descriptor table: segments do not tile "
                f"[0, {self.n_rows}) contiguously"
            )
        P = np.int64(self.payload.shape[0])
        fixed = kind == DESC_FIXED
        w_i, w_j = live[:, D_W_I], live[:, D_W_J]
        if not np.isin(w_i[fixed], (1, 2, 4)).all() or not np.isin(
            w_j[fixed], (1, 2, 4)
        ).all():
            raise ValueError(
                "torn descriptor table: fixed width not in {1, 2, 4}"
            )
        end_i = live[:, D_OFF_I].astype(np.int64) + np.where(
            fixed, w_i.astype(np.int64) * nrows, 8 * nrows.astype(np.int64)
        )
        end_j = np.where(
            fixed,
            live[:, D_OFF_J].astype(np.int64)
            + w_j.astype(np.int64) * nrows,
            0,
        )
        if (
            (live[:, D_OFF_I] < 0).any()
            or (live[:, D_OFF_J][fixed] < 0).any()
            or (end_i > P).any()
            or (end_j > P).any()
        ):
            raise ValueError(
                "torn descriptor table: segment span outside the payload"
            )
        return self


class BatchPipeline:
    """Fixed-shape batching + host/device overlap for an edge source.

    Every yielded :class:`Batch` has shape ``(batch_edges, 2)`` (PAD-padded),
    so jitted backends compile once.  ``batch_edges`` is rounded up to
    ``pad_multiple`` (the Jacobi/DMA chunk of the chunked/pallas tiers) —
    with full batches aligned to chunk boundaries, the chunked tier's
    grouping is identical to a one-shot run over the whole stream.

    ``prefetch`` batches are produced ahead on a background thread (double
    buffering by default), so file parsing / synthetic generation overlaps
    device compute.  Host edge residency is bounded by
    ``(prefetch + 1) * batch_edges`` rows of pipeline buffering plus the raw
    source slices still pinnable by re-chunking views (a slice is counted
    until a full batch of rows has arrived after it).
    :attr:`peak_buffer_bytes` tracks both levels — zero-copy views are
    counted twice, so the steady-state figure is an over- rather than
    under-estimate (transient concatenation copies are the one exclusion).
    An ``ArraySource``'s single slice is the whole array: for in-memory
    streams the metric honestly reports the resident edge list.
    """

    def __init__(
        self,
        source,
        batch_edges: int,
        *,
        pad_multiple: int = 1,
        prefetch: int = 2,
        retry: Optional[RetryPolicy] = RetryPolicy(),
        stall_timeout: Optional[float] = None,
    ):
        if batch_edges < 1:
            raise ValueError(f"batch_edges must be >= 1, got {batch_edges}")
        if pad_multiple < 1:
            raise ValueError(f"pad_multiple must be >= 1, got {pad_multiple}")
        if stall_timeout is not None and stall_timeout <= 0:
            raise ValueError(
                f"stall_timeout must be > 0 (or None), got {stall_timeout}"
            )
        self.source = source
        self.batch_edges = round_up(batch_edges, pad_multiple)
        self.prefetch = max(0, int(prefetch))
        # Resilience knobs: the pipeline re-resumes the source at the last
        # delivered row on transient read errors (retry=None disables), and
        # the consumer side of the prefetch queue raises StallError when a
        # single produce exceeds stall_timeout seconds.  The heartbeat
        # monitor brackets every producer pull so soft stalls (straggling
        # but not dead) are visible in ``stalls`` without killing the run.
        self.retry = retry
        self.stall_timeout = stall_timeout
        from repro.dist.fault_tolerance import HeartbeatMonitor

        self.heartbeat = HeartbeatMonitor()
        self.retries = 0
        self.peak_buffer_bytes = 0
        self.batches_produced = 0
        self.megabatches_produced = 0
        self._inflight_bytes = 0
        self._lock = threading.Lock()

    @property
    def stalls(self) -> int:
        """Producer pulls flagged as stragglers by the heartbeat monitor
        (soft stalls — a hard ``stall_timeout`` breach raises instead)."""
        return len(self.heartbeat.stragglers)

    def _count_retry(self, attempt: int, exc: BaseException) -> None:
        with self._lock:
            self.retries += 1

    # ------------------------------------------------------------------
    def _acquire(self, nbytes: int) -> None:
        with self._lock:
            self._inflight_bytes += nbytes
            if self._inflight_bytes > self.peak_buffer_bytes:
                self.peak_buffer_bytes = self._inflight_bytes

    def _release(self, nbytes: int) -> None:
        with self._lock:
            self._inflight_bytes -= nbytes

    def _counted_slices(self, start: Cursor) -> Iterator[np.ndarray]:
        """Pass raw source slices through while counting them toward
        residency — parse blocks / generator segments are real host memory
        even when the batches carved from them are views.

        A slice stays counted until ``batch_edges`` rows have arrived after
        it: only then can no ``rechunk`` pending-view still pin it alive.
        """
        held: deque = deque()  # (nbytes, rows) per still-pinnable slice
        held_rows = 0  # running total, so pruning is O(1) per slice
        if self.retry is not None:
            src_iter = retrying_slices(
                self.source.resume,
                self.source.cursor_at,
                start,
                self.retry,
                self._count_retry,
            )
        else:
            src_iter = iter(self.source.resume(start))
        try:
            while True:
                with span("repro.source.read"):
                    sl = next(src_iter, _END)
                if sl is _END:
                    return
                sl = np.asarray(sl)
                held.append((int(sl.nbytes), int(sl.shape[0])))
                held_rows += int(sl.shape[0])
                while len(held) > 1 and held_rows - held[0][1] >= self.batch_edges:
                    nbytes, rows = held.popleft()
                    held_rows -= rows
                    self._release(nbytes)
                self._acquire(int(sl.nbytes))
                yield sl
        finally:
            close = getattr(src_iter, "close", None)
            if close is not None:
                close()
            for nbytes, _ in held:
                self._release(nbytes)

    def _produce(self, start: Cursor) -> Iterator[Batch]:
        """Raw producer: rechunk + pad + residency accounting.  Runs on the
        prefetch thread — so source-side work (file parsing, synthetic
        generation, codec block decode) overlaps the consumer's device
        compute."""
        offset = start.row
        slices = self._counted_slices(start)
        stream = rechunk(slices, self.batch_edges)
        try:
            for raw in stream:
                padded = pad_batch(raw, self.batch_edges)
                self._acquire(padded.nbytes)
                yield Batch(edges=padded, n_rows=raw.shape[0], offset=offset)
                offset += raw.shape[0]
        finally:
            stream.close()
            slices.close()

    def batches(self, start: Union[int, Cursor] = 0) -> Iterator[Batch]:
        """Yield fixed-shape batches from a stream position — a
        :class:`~repro.graph.codecs.Cursor` (token-accelerated resume) or a
        raw row offset."""
        inner = _prefetch_iter(
            self._produce(as_cursor(start)),
            self.prefetch,
            on_drop=lambda b: self._release(b.edges.nbytes),
            heartbeat=self.heartbeat,
            stall_timeout=self.stall_timeout,
        )
        prev: Optional[Batch] = None
        try:
            for batch in inner:
                if prev is not None:
                    self._release(prev.edges.nbytes)
                prev = batch
                self.batches_produced += 1
                yield batch
        finally:
            if prev is not None:
                self._release(prev.edges.nbytes)
            inner.close()

    def _produce_mega(
        self,
        k: int,
        start: Cursor,
        wavefront: Union[int, str, None] = None,
        wavefront_gap: Optional[int] = None,
    ) -> Iterator[MegaBatch]:
        """Raw megabatch producer: stack ``k`` consecutive batches into one
        ``(k, batch_edges, 2)`` buffer.  Runs entirely on the prefetch
        thread, so the stacking memcpy (and everything upstream of it —
        parsing, generation, codec decode) overlaps the consumer's device
        dispatch.  The buffer is carved PAD-filled from the shared template
        (no per-megabatch ``np.full``), and a ragged tail keeps the full
        ``k``-batch shape with all-PAD trailing batches.

        With ``wavefront`` set, each staged buffer is additionally planned
        into node-disjoint waves (``repro.graph.wavefront.plan_waves``) here
        on the prefetch thread — the planner's host work overlaps device
        compute exactly like parsing and codec decode do.
        """
        B = self.batch_edges
        offset = start.row
        slices = self._counted_slices(start)
        stream = rechunk(slices, B)
        if wavefront is not None:
            # deferred: graph.wavefront imports this module's PAD template
            from repro.graph.wavefront import plan_waves
        try:
            while True:
                buf = None
                plan = None
                rows = 0
                n_batches = 0
                try:
                    for raw in stream:
                        m = raw.shape[0]
                        if buf is None:
                            # uninitialised on purpose: every row is either
                            # overwritten with real edges below or PAD-filled
                            # from the template before the yield
                            buf = np.empty((k, B, 2), np.int32)
                            self._acquire(buf.nbytes)
                        buf[n_batches, :m] = raw
                        if m < B:  # short final batch of the stream
                            buf[n_batches, m:] = pad_template(B - m)
                        rows += m
                        n_batches += 1
                        if n_batches == k:
                            break
                    if buf is not None and n_batches < k:
                        # ragged tail: trailing all-PAD no-op batches
                        buf[n_batches:] = pad_template(
                            (k - n_batches) * B
                        ).reshape(-1, B, 2)
                    if buf is not None and wavefront is not None:
                        plan = plan_waves(buf, wavefront, gap=wavefront_gap)
                        self._acquire(plan.nbytes)
                except BaseException:
                    # a producer error between _acquire and yield: the buffer
                    # never reaches a consumer, so unwind its accounting here
                    if plan is not None:
                        self._release(plan.nbytes)
                    if buf is not None:
                        self._release(buf.nbytes)
                    raise
                if buf is None:
                    return
                yield MegaBatch(
                    edges=buf,
                    n_rows=rows,
                    offset=offset,
                    n_batches=n_batches,
                    plan=plan,
                )
                offset += rows
                if n_batches < k:
                    return  # ragged tail: the stream is exhausted
        finally:
            stream.close()
            slices.close()

    def _produce_cmega(
        self, k: int, start: Cursor
    ) -> Iterator[CompressedMegaBatch]:
        """Raw compressed-slab producer (DESIGN.md §14): walk the source's
        sync blocks and stage *payload bytes* plus a descriptor table
        instead of decoded edges.

        Device-decodable blocks (DVE3 fixed lanes) are memcpy'd into the
        slab untouched — the host never runs their zigzag/cumsum.  Varint
        or u8 blocks, a partial first block after a mid-block resume, and
        blocks straddling the megabatch boundary are host-decoded into a
        ``carry`` buffer and staged as ``DESC_RAW`` int32 segments, split
        to the descriptor window so every segment fits one decode window.
        Decoded, the slab reproduces exactly what :meth:`_produce_mega`
        would have staged for the same rows.
        """
        B = self.batch_edges
        KB = k * B
        codec = self.source.codec
        N_win = max(1, min(int(self.source.block_rows), KB))
        D_max = KB // N_win + 3
        # capacity: 8 bytes/staged row + per-segment alignment slack, plus
        # one full decode-window span of tail slack so the kernel's
        # fixed-size descriptor DMA (payload[off : off + 8 * window + 8])
        # stays in bounds even for the last segment
        P = round_up(
            KB * _PAYLOAD_BYTES_PER_ROW
            + 2 * _SEGMENT_ALIGN * D_max
            + _PAYLOAD_BYTES_PER_ROW * N_win
            + _SEGMENT_ALIGN,
            _SEGMENT_ALIGN,
        )
        offset = start.row
        consumed = start.row  # absolute row index of the next unstaged row
        blocks = self.source.scan_blocks(start)
        carry: Optional[np.ndarray] = None  # decoded rows awaiting staging
        carry_charge = 0
        try:
            while True:
                payload: Optional[np.ndarray] = None
                desc: Optional[np.ndarray] = None
                filled = 0  # rows staged into this megabatch
                pos = 0  # payload bytes used
                nd = 0  # live descriptor rows
                fallback_rows = 0

                def ensure_buffers():
                    nonlocal payload, desc
                    if payload is None:
                        payload = np.zeros(P, np.uint8)
                        desc = np.zeros((D_max, DESC_COLS), np.int32)
                        self._acquire(payload.nbytes + desc.nbytes)

                try:
                    while filled < KB:
                        if carry is not None:
                            take = min(N_win, KB - filled, carry.shape[0])
                            ensure_buffers()
                            off = round_up(pos, _SEGMENT_ALIGN)
                            seg = np.ascontiguousarray(
                                carry[:take], dtype="<i4"
                            ).reshape(-1).view(np.uint8)
                            payload[off : off + seg.nbytes] = seg
                            desc[nd, D_KIND] = DESC_RAW
                            desc[nd, D_ROW] = filled
                            desc[nd, D_NROWS] = take
                            desc[nd, D_OFF_I] = off
                            pos = off + seg.nbytes
                            nd += 1
                            filled += take
                            fallback_rows += take
                            if take < carry.shape[0]:
                                carry = carry[take:]
                            else:
                                carry = None
                                self._release(carry_charge)
                                carry_charge = 0
                            continue
                        block = next(blocks, None)
                        if block is None:
                            break
                        skip = consumed - block.first_row
                        consumed = block.first_row + block.n_rows
                        n = block.n_rows - skip
                        meta = block.fixed
                        if (
                            meta is not None
                            and skip == 0
                            and n <= N_win
                            and filled + n <= KB
                            and -(1 << 31) <= meta.base_i < (1 << 31)
                        ):
                            ensure_buffers()
                            pay = np.frombuffer(block.payload, np.uint8)
                            off_i = round_up(pos, _SEGMENT_ALIGN)
                            li = meta.w_i * n
                            payload[off_i : off_i + li] = pay[
                                meta.off_i : meta.off_i + li
                            ]
                            off_j = round_up(off_i + li, _SEGMENT_ALIGN)
                            lj = meta.w_j * n
                            payload[off_j : off_j + lj] = pay[
                                meta.off_j : meta.off_j + lj
                            ]
                            desc[nd] = (
                                DESC_FIXED,
                                filled,
                                n,
                                off_i,
                                off_j,
                                meta.w_i,
                                meta.w_j,
                                meta.base_i,
                            )
                            pos = off_j + lj
                            nd += 1
                            filled += n
                        else:
                            rows = codec.decode_block(
                                block.payload, block.n_rows, block.version
                            )
                            if skip:
                                rows = rows[skip:]
                            carry = rows
                            carry_charge = int(rows.nbytes)
                            self._acquire(carry_charge)
                except BaseException:
                    if payload is not None:
                        self._release(payload.nbytes + desc.nbytes)
                    raise
                if filled == 0:
                    return
                yield CompressedMegaBatch(
                    payload=payload,
                    desc=desc,
                    n_rows=filled,
                    offset=offset,
                    n_batches=-(-filled // B),
                    n_desc=nd,
                    window=N_win,
                    fallback_rows=fallback_rows,
                    out_rows=KB,
                )
                offset += filled
                if filled < KB:
                    return  # ragged tail: the stream is exhausted
        finally:
            if carry is not None:
                self._release(carry_charge)
            blocks.close()

    def compressed_megabatches(
        self, k: int, start: Union[int, Cursor] = 0
    ) -> Iterator[CompressedMegaBatch]:
        """Yield compressed-slab megabatches from a stream position.

        The device-decode analogue of :meth:`megabatches`: identical row
        coverage per megabatch (``k * batch_edges`` rows from the same
        start), but the staged buffer holds compressed payload bytes plus
        a :data:`DESC_COLS`-column descriptor table; decoding it on device
        (or via the pure-JAX reference) reconstructs the exact
        ``(k, batch_edges, 2)`` PAD-carved slab.  Requires a block-codec
        file source (a ``.dvc`` behind :class:`CodecFileSource`).
        """
        if k < 1:
            raise ValueError(f"megabatch k must be >= 1, got {k}")
        if getattr(self.source, "block_rows", None) is None or not hasattr(
            self.source, "scan_blocks"
        ):
            raise ValueError(
                "compressed staging needs a block-codec file source "
                "(CodecFileSource over a dvc file)"
            )
        inner = _prefetch_iter(
            self._produce_cmega(k, as_cursor(start)),
            self.prefetch,
            on_drop=lambda cm: self._release(cm.payload.nbytes + cm.desc.nbytes),
            heartbeat=self.heartbeat,
            stall_timeout=self.stall_timeout,
        )
        prev: Optional[CompressedMegaBatch] = None
        try:
            for cm in inner:
                if prev is not None:
                    self._release(prev.payload.nbytes + prev.desc.nbytes)
                prev = cm
                self.megabatches_produced += 1
                self.batches_produced += cm.n_batches
                yield cm
        finally:
            if prev is not None:
                self._release(prev.payload.nbytes + prev.desc.nbytes)
            inner.close()

    @staticmethod
    def _mega_nbytes(mb: MegaBatch) -> int:
        """Residency charged for one staged megabatch (edges + wave plan)."""
        return mb.edges.nbytes + (mb.plan.nbytes if mb.plan is not None else 0)

    def megabatches(
        self,
        k: int,
        start: Union[int, Cursor] = 0,
        *,
        wavefront: Union[int, str, None] = None,
        wavefront_gap: Optional[int] = None,
    ) -> Iterator[MegaBatch]:
        """Yield ``(k, batch_edges, 2)`` megabatches from a stream position.

        The fused-dispatch analogue of :meth:`batches`: identical batch
        boundaries (``rechunk`` by ``batch_edges`` from the same start row),
        so a megabatch is exactly the concatenation of the next ``k``
        :meth:`batches` results — which is what makes the fused device paths
        bit-identical to per-batch ingestion.  Residency accounting counts
        each staged ``k``-batch buffer — plus its wave plan when
        ``wavefront`` is set — so ``peak_buffer_bytes`` honestly reflects
        the larger staging footprint.
        """
        if k < 1:
            raise ValueError(f"megabatch k must be >= 1, got {k}")
        if (
            wavefront is not None
            and not isinstance(wavefront, str)
            and wavefront < 1
        ):
            raise ValueError(f"wavefront width must be >= 1, got {wavefront}")
        inner = _prefetch_iter(
            self._produce_mega(k, as_cursor(start), wavefront, wavefront_gap),
            self.prefetch,
            on_drop=lambda mb: self._release(self._mega_nbytes(mb)),
            heartbeat=self.heartbeat,
            stall_timeout=self.stall_timeout,
        )
        prev: Optional[MegaBatch] = None
        try:
            for mega in inner:
                if prev is not None:
                    self._release(self._mega_nbytes(prev))
                prev = mega
                self.megabatches_produced += 1
                self.batches_produced += mega.n_batches
                yield mega
        finally:
            if prev is not None:
                self._release(self._mega_nbytes(prev))
            inner.close()

    def __iter__(self) -> Iterator[Batch]:
        return self.batches()


def _prefetch_iter(
    gen: Iterator,
    depth: int,
    on_drop=None,
    heartbeat=None,
    stall_timeout: Optional[float] = None,
) -> Iterator:
    """Run ``gen`` up to ``depth`` items ahead on one background thread.

    The single worker pulls items sequentially (generators are not
    thread-safe — one puller only); at most ``depth`` results are buffered,
    so producer memory stays bounded even if the consumer stalls.  On early
    close, items already produced but never consumed are handed to
    ``on_drop`` so the caller can undo any per-item accounting.

    A producer exception (decode error, torn file, generator bug) is
    captured on the worker, the queue of already-produced items is drained
    through ``on_drop``, the worker thread is *joined*, and only then is the
    exception re-raised on the consumer — so a failure mid-stream can never
    leave a dangling producer thread or leaked residency accounting behind
    the caller's back.

    ``heartbeat`` (a :class:`repro.dist.fault_tolerance.HeartbeatMonitor`)
    brackets each producer pull, so straggling produces show up as soft
    stalls without killing the run.  ``stall_timeout`` is the hard
    watchdog: when the *consumer* has waited more than that many seconds
    for the next item, :class:`~repro.graph.errors.StallError` is raised
    (a wedged worker cannot be interrupted, but it holds no further
    items: the queue is drained and the run fails loudly instead of
    hanging forever).  Neither applies on the synchronous ``depth <= 0``
    path, where there is no worker to watch.
    """
    if depth <= 0:
        try:
            while True:
                with span("repro.pipeline.stage"):
                    item = next(gen, _END)
                if item is _END:
                    return
                yield item
        finally:
            gen.close()
    # the thread's name shows in thread dumps (``threading.enumerate``)
    ex = ThreadPoolExecutor(max_workers=1, thread_name_prefix="repro-prefetch")
    pulls = 0

    def pull():
        # Capture *every* outcome as a tagged pair: the consumer must be
        # able to tell produced items (which need on_drop accounting if
        # never consumed) from terminal signals without re-raising inside
        # the cleanup path.
        nonlocal pulls
        if heartbeat is not None:
            heartbeat.step_start()
        try:
            try:
                with span("repro.pipeline.stage"):
                    out = ("item", next(gen))
            except StopIteration:
                out = ("stop", None)
            except BaseException as e:  # propagated on consumer after join
                out = ("raise", e)
        finally:
            if heartbeat is not None:
                heartbeat.step_end(pulls)
                pulls += 1
        return out

    futures: deque = deque()
    stalled = False
    try:
        for _ in range(depth):
            futures.append(ex.submit(pull))
        while futures:
            try:
                with span("repro.pipeline.wait"):
                    kind, value = futures[0].result(timeout=stall_timeout)
            except _FuturesTimeout:
                stalled = True
                raise StallError(
                    f"prefetch producer stalled: no item within "
                    f"{stall_timeout}s (source wedged or deadlocked)"
                ) from None
            futures.popleft()
            if kind == "stop":
                break
            if kind == "raise":
                # the finally below drains the queue and joins the worker
                # before this leaves the generator
                raise value
            futures.append(ex.submit(pull))
            yield value
    finally:
        for f in futures:
            if not f.cancel():
                if stalled and not f.done():
                    continue  # wedged worker: never block cleanup on it
                kind, value = f.result()
                if kind == "item" and on_drop is not None:
                    on_drop(value)
        # A wedged worker cannot be joined and its generator frame cannot
        # be closed from here ("generator already executing") — leak the
        # thread and let the StallError surface; every healthy path still
        # joins and closes.
        ex.shutdown(wait=not stalled)
        if not stalled:
            gen.close()
