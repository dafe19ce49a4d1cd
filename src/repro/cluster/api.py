"""The unified public clustering API: ``cluster`` / ``StreamClusterer``.

One call — ``cluster(edges, ClusterConfig(...))`` — dispatches through the
backend registry; ``StreamClusterer`` exposes the same engine incrementally
(``partial_fit`` per arriving batch, ``fit`` to drain an
:class:`~repro.graph.sources.EdgeSource`, ``finalize`` for the result), with
the backend's state pytree suspendable to disk via
``repro.checkpoint.manager`` and resumable in a later session — including
mid-stream: checkpoints record the stream :class:`~repro.graph.codecs
.Cursor` (raw row + the source's opaque resume token), so ``restore`` +
``fit(source)`` picks up an out-of-core file — raw, delta+varint
compressed, or a multi-stream merge — exactly where the previous session
stopped.

*Resumable + out-of-core is the invariant, not the special case*: every
backend threads a state pytree (``ClusterState`` / ``SweepState`` /
``ShardedState`` — see ``Backend.state_kind``) through ``partial_fit``, so
the §2.5 multi-parameter sweep and the sharded distributed tier stream,
checkpoint, and resume exactly like the single-parameter tiers.  Backends
with a ``finalize_fn`` (sweep selection, shard merge) derive labels from
state at finalize time; the :class:`Clustering` they return always carries a
plain :class:`ClusterState` view, so the edge-free metrics are uniform.

``edges`` everywhere means *array, path, or EdgeSource*: in-memory arrays
auto-wrap (and keep the historical one-shot path), file/generator sources
stream through the :class:`~repro.graph.pipeline.BatchPipeline` with host
edge residency bounded by O(``batch_edges``) while the state stays the
paper's ``3n`` ints (``(2A+1) n`` for the sweep, ``3Pn`` for ``P`` shards).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.metrics import community_stats, entropy_from_state
from repro.core.state import ClusterState, ShardedState, SweepState
from repro.core.streaming import canonical_labels
from repro.cluster.config import ClusterConfig
from repro.cluster.refine import RefineRuntime
from repro.cluster.registry import Backend, BackendResult, get_backend
from repro.graph.codecs import Cursor
from repro.graph.errors import RetryPolicy
from repro.graph.pipeline import D_KIND, DESC_RAW, BatchPipeline
from repro.graph.wavefront import plan_waves
from repro.graph.sources import ArraySource, EdgeSource, as_source
from repro.obs import span, spanned

_CONFIG_FILE = "cluster_config.json"

# Default edges per ingest batch when streaming from a source (8 MB of int32
# pairs — small against any graph worth streaming, large enough to keep the
# device tiers fed).
DEFAULT_BATCH_EDGES = 1 << 20

_EMPTY_BATCH = np.zeros((0, 2), np.int32)


def _make_pipeline(
    source: EdgeSource, config: ClusterConfig, backend: Backend
) -> BatchPipeline:
    """The ingest pipeline for one run: fixed batch shape (one jit compile),
    chunk-aligned for the Jacobi/DMA tiers so batching never moves a chunk
    boundary (labels match the one-shot run even for ``chunked``), prefetch
    depth per config (``None`` defers to the pipeline's own default)."""
    kwargs: Dict[str, Any] = {}
    if config.prefetch is not None:
        kwargs["prefetch"] = config.prefetch
    if config.retries is not None:
        # 0 disables retry outright; k bounds consecutive attempts per fault
        kwargs["retry"] = (
            RetryPolicy(max_retries=config.retries) if config.retries else None
        )
    if config.stall_timeout is not None:
        kwargs["stall_timeout"] = config.stall_timeout
    return BatchPipeline(
        source,
        config.batch_edges or DEFAULT_BATCH_EDGES,
        pad_multiple=config.chunk if backend.chunk_aligned else 1,
        **kwargs,
    )


def _resolve_config(
    config: ClusterConfig, backend: Backend, mesh=None, state=None
) -> ClusterConfig:
    """Pin config fields the state shape depends on.  The sharded tier's
    ``n_shards`` must be concrete before ``init_fn`` runs (it is the leading
    state axis): a carried state fixes it, a ``mesh`` contributes its device
    count, otherwise the visible device count is used."""
    if backend.state_kind == "sharded" and config.n_shards is None:
        # getattr: a wrong-kind state has no n_shards — fall through so
        # _check_state can report the kind mismatch instead of crashing here
        if state is not None and getattr(state, "n_shards", None) is not None:
            n_shards = state.n_shards
        elif mesh is not None:
            from repro.core.distributed import mesh_shards

            n_shards = mesh_shards(mesh)
        else:
            import jax

            n_shards = jax.device_count()
        config = config.replace(n_shards=n_shards)
    return config


def _state_kind_of(state) -> str:
    """Kind of a state pytree.  The wide kinds are *defined* by their
    classes (a backend declaring ``state_kind="sweep"``/``"sharded"`` must
    thread ``SweepState``/``ShardedState``); everything else — including
    third-party custom states — is the open ``"cluster"`` kind."""
    if isinstance(state, SweepState):
        return "sweep"
    if isinstance(state, ShardedState):
        return "sharded"
    return "cluster"


def _check_state(state, config: ClusterConfig, backend: Backend) -> None:
    """A carried state must match the config's shape parameters — dispatched
    on the backend's state kind rather than assuming ``ClusterState``."""
    got_kind = _state_kind_of(state)
    if got_kind != backend.state_kind:
        raise ValueError(
            f"backend {backend.name!r} threads a {backend.state_kind} state "
            f"but was given a {got_kind} state; states are not "
            "interchangeable across kinds"
        )
    if state.n != config.n:
        raise ValueError(
            f"state has n={state.n} but config.n={config.n}; a carried "
            "state must come from a run with the same node-id space"
        )
    if backend.state_kind == "sweep":
        got = tuple(int(x) for x in np.asarray(state.v_maxes))
        if got != tuple(config.v_maxes):
            raise ValueError(
                f"sweep state was built for v_maxes={got} but config has "
                f"v_maxes={tuple(config.v_maxes)}; a resumed sweep cannot "
                "silently continue under different parameters"
            )
    elif backend.state_kind == "sharded":
        if state.n_shards != config.n_shards:
            raise ValueError(
                f"sharded state has n_shards={state.n_shards} but config "
                f"has n_shards={config.n_shards}; shard count is a state "
                "dimension and cannot change mid-run"
            )


class Clustering:
    """A clustering result: labels + edge-free metrics (paper §2.5).

    ``state`` is always a plain :class:`ClusterState` view of the result
    (for the sweep: the selected column; for the sharded tier: the merged
    state), whatever the backend's internal state kind — so the edge-free
    metrics below work uniformly across all seven tiers.  Everything
    derivable is lazy/cached so benchmarks can time the backends without
    paying for canonicalisation or metrics they don't read.
    """

    def __init__(
        self,
        state: Optional[ClusterState],
        config: ClusterConfig,
        raw_labels,
        info: Optional[Dict[str, Any]] = None,
    ):
        self.state = state
        self.config = config
        self.raw_labels = raw_labels
        self.info = dict(info or {})
        self._labels: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    @property
    def labels(self) -> np.ndarray:
        """Canonical labels 0..K-1 by first appearance (cross-backend
        comparable)."""
        if self._labels is None:
            with span("repro.labels"):
                self._labels = canonical_labels(np.asarray(self.raw_labels))
        return self._labels

    @property
    def entropy(self) -> Optional[float]:
        """H over community volumes — edge-free, from ``(v, sum d)`` alone.
        ``None`` only if a third-party backend returns no state."""
        if self.state is None:
            return None
        v = np.asarray(self.state.v)
        w = float(np.asarray(self.state.d).sum())
        return entropy_from_state(v, w) if w > 0 else 0.0

    @property
    def avg_density(self) -> Optional[float]:
        """Average community density — edge-free, from ``(c, v)`` alone.

        Works in any backend label space by looking up each node's community
        volume (dense: ``v[label]``; oracle: ``v[label - 1]``, synthesized
        singleton labels for never-seen nodes have volume 0)."""
        if self.state is None:
            return None
        v = np.asarray(self.state.v)
        raw = np.asarray(self.raw_labels)
        space = get_backend(self.config.backend).label_space
        idx = raw - 1 if space == "oracle" else raw
        in_bounds = (idx >= 0) & (idx < v.shape[0])
        node_vol = np.where(in_bounds, v[np.clip(idx, 0, v.shape[0] - 1)], 0)
        _, first, counts = np.unique(raw, return_index=True, return_counts=True)
        vol_u = node_vol[first].astype(np.float64)
        pairs = np.maximum(counts * (counts - 1.0), 1.0)
        dens = np.where(counts > 1, vol_u / pairs, 0.0)
        return float(dens.mean()) if dens.size else 0.0

    @property
    def peak_buffer_bytes(self) -> Optional[int]:
        """Measured peak host edge-buffer residency of the run that produced
        this result (``None`` for non-streamed runs).  Scales with the
        configured pipeline depth: ``(prefetch + 1) * batch_edges`` rows per
        buffered (mega)batch, times ``megabatch_k`` in megabatch mode."""
        v = self.info.get("peak_buffer_bytes")
        return None if v is None else int(v)

    @property
    def community_stats(self) -> Dict[str, float]:
        return community_stats(self.labels)

    @property
    def n_communities(self) -> int:
        return int(self.community_stats["n_communities"])

    def block_until_ready(self) -> "Clustering":
        if self.state is not None:
            self.state.block_until_ready()
        return self

    def __repr__(self) -> str:
        return (
            f"Clustering(backend={self.config.backend!r}, n={self.config.n}, "
            f"edges_seen={int(self.state.edges_seen) if self.state else '?'})"
        )


def cluster(
    edges,
    config: ClusterConfig,
    *,
    state=None,
    mesh=None,
) -> Clustering:
    """Cluster an edge stream in one call, via ``config.backend``.

    Args:
      edges: the stream, in stream order (PAD rows are no-ops) — a (m, 2)
        int array, a file path, or any :class:`repro.graph.sources
        .EdgeSource`.  Out-of-core sources are ingested in
        ``config.batch_edges``-sized batches through the resumable
        ``partial_fit`` machinery (host edge residency O(batch), labels
        identical to the in-memory run); arrays take the historical one-shot
        path unless ``batch_edges`` is set.  The sharded tier always streams
        (batches are its unit of shard assignment): with ``batch_edges``
        unset the stream is counted once and split into one window per
        shard (capped at the default batch size, which stripes longer
        streams across shards out-of-core).
      config: validated :class:`ClusterConfig`.
      state: optional carried state pytree (see ``Backend.state_kind``);
        fresh state is created when omitted.  Must come from a run with the
        same shape parameters (``n``; sweep ``v_maxes``; shard count) and
        the same backend label space.  Treated as *consumed*: the device
        tiers donate their state buffers, so a device-resident state passed
        here must not be reused afterwards (host/numpy states are copied at
        dispatch and stay valid).
      mesh: optional ``jax.sharding.Mesh`` — contributes its device count as
        the default ``n_shards`` for ``backend="distributed"``.

    Returns:
      a :class:`Clustering` bundling labels, state, and edge-free metrics.
      Streamed runs add ``info["peak_buffer_bytes"]`` /
      ``info["stream_batches"]`` (the paper's memory story, measured).
    """
    source = as_source(edges)
    backend = get_backend(config.backend)
    config = _resolve_config(config, backend, mesh, state)
    if state is None:
        state = backend.init_fn(config)
    _check_state(state, config, backend)

    in_memory = isinstance(source, ArraySource)
    # The sharded tier always streams — batches are its unit of shard
    # assignment (fit() sizes the default window per shard).  Refined runs
    # always stream too: the supergraph sketch is accumulated per ingested
    # batch, so the one-shot array path would never feed it.
    if (
        backend.state_kind == "sharded"
        or config.refine is not None
        or config.autosave_every is not None
        or (
            backend.resumable
            and (not in_memory or config.batch_edges is not None)
        )
    ):
        # One drain implementation for both entry points: the incremental
        # clusterer owns the pipeline lifecycle (close-on-error, residency
        # bookkeeping, info surfacing).
        return StreamClusterer(config, state=state).fit(source).finalize()

    if not in_memory:
        raise ValueError(
            f"backend {config.backend!r} is not resumable and cannot ingest "
            "an out-of-core source; materialize the stream yourself or use "
            "a resumable backend"
        )
    result = backend.fn(source.edges, config, state, mesh=mesh)
    if backend.finalize_fn is not None:
        result = backend.finalize_fn(result.state, config)
    return Clustering(
        state=result.state,
        config=config,
        raw_labels=result.labels,
        info=result.info,
    )


class StreamClusterer:
    """Incremental ingestion: ``partial_fit`` per arriving edge batch, or
    :meth:`fit` to drain an :class:`~repro.graph.sources.EdgeSource`.

    The production streaming scenario — edges arrive over time, state is the
    backend's state pytree (the paper's ``3n`` ints; ``(2A+1) n`` for the
    sweep; ``3Pn`` for ``P`` shards), and the run can be suspended
    (:meth:`save`) and resumed (:meth:`restore`) across processes —
    including mid-stream: the checkpoint records :attr:`stream_cursor` (raw
    source rows consumed plus the source's opaque resume token), so a
    restored clusterer's :meth:`fit` continues an out-of-core file from the
    exact row the previous session stopped at — seeking straight to a
    recorded sync point for compressed/text streams.
    Every built-in backend supports ``partial_fit``; for the
    strictly-sequential tiers (sweep included) the result is identical to
    one :func:`cluster` call over the concatenated stream, regardless of
    batching.
    """

    def __init__(self, config: ClusterConfig, state=None):
        self._backend: Backend = get_backend(config.backend)
        config = _resolve_config(config, self._backend, state=state)
        self.config = config
        if not self._backend.resumable:
            raise ValueError(
                f"backend {config.backend!r} does not support incremental "
                "partial_fit; use cluster() for one-shot runs"
            )
        if state is None:
            with span("repro.clusterer.init"):
                state = self._backend.init_fn(config)
        _check_state(state, config, self._backend)
        self._state = state
        self._last_result = None
        # Post-stream refinement (DESIGN.md §11): the runtime owns the
        # supergraph sketch (one per sweep column) and the optional replay
        # window; both are observed per dispatch and ride checkpoints.
        self._refine: Optional[RefineRuntime] = (
            RefineRuntime(config, self._backend)
            if config.refine is not None
            else None
        )
        self._cursor = Cursor(0)
        self.peak_buffer_bytes = 0
        self.stream_batches = 0
        self.stream_megabatches = 0
        # Device dispatches issued (one per partial_fit / fused megabatch) —
        # the denominator of the dispatch-amortisation story: megabatch mode
        # drops this ~K-fold for the same stream_batches.
        self.stream_dispatches = 0
        # Wavefront-mode counters (DESIGN.md §12), accumulated per planned
        # megabatch; surfaced by finalize() as the mean-wave-width /
        # fallback-rate / planner-overhead info entries.
        self.wavefront_megabatches = 0
        self.wavefront_waves = 0
        self.wavefront_rows_in_waves = 0
        self.wavefront_leftover_rows = 0
        self.wavefront_dead_rows_skipped = 0
        self.wavefront_plan_seconds = 0.0
        # adaptive widths chosen per planned megabatch (wavefront="auto";
        # fixed-W runs record the fixed width) — surfaced as the
        # ``wavefront_widths`` info counter
        self.wavefront_widths: list = []
        # (2,) device array [live_waves, fallback_waves], accumulated as lazy
        # device adds — no host sync until finalize() reads it
        self._wavefront_stats = None
        # Device-decode counters (DESIGN.md §14), accumulated per compressed
        # megabatch dispatched through the backend's decode_fn
        self.device_decoded_megabatches = 0
        self.device_fallback_rows = 0
        self.device_fallback_segments = 0
        self.device_total_segments = 0
        # Resilience counters (DESIGN.md §15): autosaves taken from inside
        # fit, transient-read retries and soft stalls observed by the ingest
        # pipeline, and the quarantine accounting of every checksummed
        # source this clusterer has drained — all surfaced by finalize().
        self.autosaves = 0
        self.ingest_retries = 0
        self.ingest_stalls = 0
        self._last_autosave_row = 0
        self._quarantine_sources: list = []

    # ------------------------------------------------------------------
    @property
    def state(self):
        return self._state

    @property
    def edges_seen(self) -> int:
        return int(self._state.edges_seen)

    @property
    def stream_cursor(self) -> Cursor:
        """The stream position as an opaque :class:`Cursor` — raw rows
        ingested plus whatever resume token the source minted for that row
        (block sync byte offsets for compressed files, per-source offsets
        for merged streams).  A leaf of every checkpoint."""
        return self._cursor

    @property
    def stream_offset(self) -> int:
        """Raw source rows ingested so far (counts PAD/self-loop rows too —
        this is a *stream position*, unlike ``edges_seen`` which counts live
        edges only).  The row coordinate of :attr:`stream_cursor`."""
        return self._cursor.row

    def partial_fit(self, edge_batch, *, raw_rows: Optional[int] = None) -> "StreamClusterer":
        """Ingest one batch of edges; returns ``self`` for chaining.

        ``raw_rows``: how many raw stream rows this batch represents (defaults
        to the batch length) — :meth:`fit` passes the pre-padding row count so
        ``stream_offset`` tracks the source, not the padded device shape.
        Directly pushed batches advance the cursor row with an empty token
        (there is no source to mint one); :meth:`fit` refreshes the token
        from its source after every batch.
        """
        with span("repro.dispatch"):
            result = self._backend.fn(edge_batch, self.config, self._state)
        self._state = result.state
        self._last_result = result
        if self._refine is not None:
            self._refine.observe(self._state, edge_batch)
        rows = int(raw_rows if raw_rows is not None else np.shape(edge_batch)[0])
        self._cursor = Cursor(self._cursor.row + rows)
        self.stream_dispatches += 1
        return self

    def partial_fit_megabatch(
        self, edge_batches, *, raw_rows: Optional[int] = None, plan=None
    ) -> "StreamClusterer":
        """Ingest ``(K, B, 2)`` stacked fixed-shape batches in *one* fused
        device dispatch; returns ``self`` for chaining.

        Requires the backend to register a ``megabatch_fn`` (``chunked``:
        one ``lax.scan`` over all K·B/chunk Jacobi chunks; ``pallas``: one
        double-buffered-DMA kernel launch) — results are bit-identical to
        ``K`` sequential :meth:`partial_fit` calls over the same batches,
        and trailing all-PAD batches are no-ops, so ragged tails ride the
        same shape.  ``raw_rows`` is the raw-source row count the megabatch
        represents (defaults to ``K * B``, the padded shape); :meth:`fit`
        passes the pre-padding count so the cursor tracks the source.

        With ``config.wavefront`` set and a backend that registers a
        ``wavefront_fn`` (``pallas``), the megabatch is dispatched through
        the wavefront path instead (DESIGN.md §12): ``plan`` is the
        :class:`~repro.graph.wavefront.WavePlan` staged by the pipeline's
        prefetch thread (:meth:`fit` passes it), or is computed inline here
        for directly pushed megabatches.  Labels stay bit-identical; the
        plan/fallback counters accumulate on this clusterer and surface in
        :meth:`finalize`'s info.  Backends without a wavefront path ignore
        the knob and take the sequential fused path.
        """
        if self._backend.megabatch_fn is None:
            raise ValueError(
                f"backend {self.config.backend!r} has no fused megabatch "
                "path; use partial_fit per batch"
            )
        use_wave = (
            self.config.wavefront is not None
            and self._backend.wavefront_fn is not None
        )
        if use_wave:
            if plan is None:
                plan = plan_waves(
                    np.asarray(edge_batches),
                    self.config.wavefront,
                    gap=self.config.wavefront_gap,
                )
            with span("repro.dispatch"):
                result = self._backend.wavefront_fn(plan, self.config, self._state)
            stats = result.info.pop("wavefront_stats", None)
            if stats is not None:
                # lazy device add — host sync deferred to finalize()
                self._wavefront_stats = (
                    stats
                    if self._wavefront_stats is None
                    else self._wavefront_stats + stats
                )
            self.wavefront_megabatches += 1
            self.wavefront_waves += plan.n_waves
            self.wavefront_rows_in_waves += plan.rows_in_waves
            self.wavefront_leftover_rows += plan.leftover_rows
            self.wavefront_dead_rows_skipped += plan.dead_rows_skipped
            self.wavefront_plan_seconds += plan.plan_seconds
            self.wavefront_widths.append(int(plan.width))
        else:
            with span("repro.dispatch"):
                result = self._backend.megabatch_fn(
                    edge_batches, self.config, self._state
                )
        self._state = result.state
        self._last_result = result
        if self._refine is not None:
            # sketch observation follows dispatch granularity: one label
            # fetch per fused megabatch, all K*B edges bucketed under the
            # post-megabatch labels
            self._refine.observe(self._state, edge_batches)
        K = int(np.shape(edge_batches)[0])
        B = int(np.shape(edge_batches)[1])
        rows = int(raw_rows if raw_rows is not None else K * B)
        self._cursor = Cursor(self._cursor.row + rows)
        self.stream_dispatches += 1
        self.stream_megabatches += 1
        return self

    def partial_fit_cmegabatch(self, cmega) -> "StreamClusterer":
        """Ingest one :class:`~repro.graph.pipeline.CompressedMegaBatch` —
        DVE3 payload bytes plus a descriptor table — through the backend's
        device decode path (DESIGN.md §14); returns ``self`` for chaining.

        One fused decode→update dispatch per call, exactly like
        :meth:`partial_fit_megabatch` dispatches once per staged megabatch;
        labels are bit-identical to host-decoding the same rows, and the
        cursor advances by the same raw row count, so checkpoints taken on
        either path resume cleanly into the other.
        """
        if self._backend.decode_fn is None:
            raise ValueError(
                f"backend {self.config.backend!r} has no device decode "
                "path; use partial_fit_megabatch with host-decoded edges"
            )
        with span("repro.dispatch"):
            result = self._backend.decode_fn(
                cmega.validate(), self.config, self._state
            )
        self._state = result.state
        self._last_result = result
        self._cursor = Cursor(self._cursor.row + int(cmega.n_rows))
        self.stream_dispatches += 1
        self.stream_megabatches += 1
        self.device_decoded_megabatches += 1
        self.device_fallback_rows += int(cmega.fallback_rows)
        kinds = np.asarray(cmega.desc[: cmega.n_desc, D_KIND])
        self.device_fallback_segments += int(
            np.count_nonzero(kinds == DESC_RAW)
        )
        self.device_total_segments += int(cmega.n_desc)
        return self

    def _autosave_due(self, config: ClusterConfig) -> bool:
        return (
            config.autosave_every is not None
            and self._cursor.row - self._last_autosave_row
            >= config.autosave_every
        )

    def _autosave(self, config: ClusterConfig) -> None:
        self.save(config.autosave_dir)
        self._last_autosave_row = self._cursor.row
        self.autosaves += 1

    @spanned("repro.fit")
    def fit(
        self,
        edges,
        *,
        max_batches: Optional[int] = None,
        preemption=None,
    ) -> "StreamClusterer":
        """Stream a source through ``partial_fit`` from :attr:`stream_offset`.

        ``edges``: array, path, or :class:`~repro.graph.sources.EdgeSource`.
        Ingestion starts at the current :attr:`stream_offset` (0 for a fresh
        clusterer), so calling ``fit`` with the same source after a
        :meth:`restore` resumes mid-stream rather than replaying.
        ``max_batches`` bounds this call (suspend points for cooperative
        preemption); returns ``self``.

        With ``config.megabatch_k = K`` set and a backend that registers a
        fused ``megabatch_fn`` (``chunked``, ``pallas``), ingestion runs in
        *megabatch mode*: the pipeline stages ``K`` consecutive batches into
        one ``(K, batch_edges, 2)`` host buffer on its prefetch thread and
        the device is dispatched once per megabatch — ~K-fold fewer
        dispatches/transfers, labels bit-identical to per-batch ingestion,
        and the stream cursor still lands on exact batch-row boundaries (so
        checkpoints taken at any per-batch suspend point resume cleanly
        into megabatch mode, and vice versa).  A ``max_batches`` budget that
        is not a megabatch multiple drains the remainder per-batch.

        For the sharded tier with ``batch_edges`` unset, the stream is
        counted once and the batch sized to one window per shard (capped at
        the default batch size, which stripes longer streams) — batches are
        that tier's unit of shard assignment, so a single giant batch would
        silently pile the whole stream onto shard 0.  The sizing depends
        only on the source length, so resumed sessions deal identically.

        ``preemption``: an optional
        :class:`~repro.dist.fault_tolerance.PreemptionHandler` polled after
        every ingested (mega)batch — once it fires, the in-flight unit is
        drained, a final checkpoint is written (when ``autosave_dir`` is
        configured), and ``fit`` returns early with the cursor on an exact
        resume point.  Combined with ``config.autosave_every`` this is the
        crash-recovery story: a killed run restores from the newest valid
        generation and finishes with labels bit-identical to an
        uninterrupted one.
        """
        source = as_source(edges)
        config = self.config
        if config.on_corrupt == "quarantine" and getattr(
            source, "supports_quarantine", False
        ):
            # policy is config-driven at fit time: the source skips corrupt
            # blocks to the next sync marker and counts the loss instead of
            # raising (sources without checksummed framing keep raising)
            source.on_corrupt = "quarantine"
        if getattr(source, "supports_quarantine", False) and all(
            s is not source for s in self._quarantine_sources
        ):
            self._quarantine_sources.append(source)
        if self._backend.state_kind == "sharded" and config.batch_edges is None:
            m = source.count_edges()
            per_shard = max(1, -(-m // config.n_shards))
            config = config.replace(
                batch_edges=min(per_shard, DEFAULT_BATCH_EDGES)
            )
        pipe = _make_pipeline(source, config, self._backend)
        K = config.megabatch_k
        use_mega = (
            K is not None
            and K > 1
            and self._backend.megabatch_fn is not None
        )
        # Device-resident compressed ingest (DESIGN.md §14): stage payload
        # bytes + descriptor tables and let the backend's decode_fn unpack
        # them on device.  Requires a block-codec source — anything else
        # (arrays, text files) falls through to host-decoded staging, so
        # device_decode=True is safe to set unconditionally.
        use_cmega = (
            use_mega
            and config.device_decode
            and self._backend.decode_fn is not None
            and getattr(source, "block_rows", None) is not None
            and hasattr(source, "scan_blocks")
        )
        n = 0
        exhausted = False
        stop = False  # preemption fired: drain-in-flight done, exit early
        if use_cmega and (max_batches is None or max_batches >= K):
            cmegas = pipe.compressed_megabatches(K, start=self._cursor)
            try:
                exhausted = True  # flipped back if we stop for the budget
                for cm in cmegas:
                    self.partial_fit_cmegabatch(cm)
                    # refresh the resume token (see the per-batch loop below)
                    self._cursor = source.cursor_at(self._cursor.row)
                    n += cm.n_batches
                    if self._autosave_due(config):
                        self._autosave(config)
                    if preemption is not None and preemption.preempted:
                        stop = True
                        break
                    if cm.n_batches < K:
                        break  # ragged tail: the stream is exhausted
                    if max_batches is not None and max_batches - n < K:
                        exhausted = False
                        break
            finally:
                cmegas.close()
        elif use_mega and (max_batches is None or max_batches >= K):
            # waves are planned on the pipeline's prefetch thread while the
            # megabatch is staged (None when the backend has no wavefront_fn
            # or the knob is unset — partial_fit_megabatch then ignores it)
            wf = (
                config.wavefront
                if self._backend.wavefront_fn is not None
                else None
            )
            megas = pipe.megabatches(
                K,
                start=self._cursor,
                wavefront=wf,
                wavefront_gap=(
                    config.wavefront_gap if wf is not None else None
                ),
            )
            try:
                exhausted = True  # flipped back if we stop for the budget
                for mega in megas:
                    self.partial_fit_megabatch(
                        mega.edges, raw_rows=mega.n_rows, plan=mega.plan
                    )
                    # refresh the resume token (see the per-batch loop below)
                    self._cursor = source.cursor_at(self._cursor.row)
                    n += mega.n_batches
                    if self._autosave_due(config):
                        self._autosave(config)
                    if preemption is not None and preemption.preempted:
                        stop = True
                        break
                    if mega.n_batches < K:
                        break  # ragged tail: the stream is exhausted
                    if max_batches is not None and max_batches - n < K:
                        # not enough budget for another full megabatch; any
                        # remainder drains per-batch below
                        exhausted = False
                        break
            finally:
                megas.close()
        if not stop and not exhausted and (
            max_batches is None or n < max_batches
        ):
            batches = pipe.batches(start=self._cursor)
            try:
                for batch in batches:
                    self.partial_fit(batch.edges, raw_rows=batch.n_rows)
                    # refresh the resume token: the source knows the best
                    # sync point (codec block, text byte offset, merge
                    # positions) for the row partial_fit just advanced to
                    self._cursor = source.cursor_at(self._cursor.row)
                    n += 1
                    if self._autosave_due(config):
                        self._autosave(config)
                    if preemption is not None and preemption.preempted:
                        stop = True
                        break
                    if max_batches is not None and n >= max_batches:
                        break
            finally:
                # deterministic suspension: shut the prefetch thread down
                # before reading the residency figure or returning control
                batches.close()
        self.peak_buffer_bytes = max(
            self.peak_buffer_bytes, pipe.peak_buffer_bytes
        )
        self.stream_batches += n
        self.ingest_retries += pipe.retries
        self.ingest_stalls += pipe.stalls
        if (
            stop
            and config.autosave_dir
            and self._last_autosave_row != self._cursor.row
        ):
            # preemption drain: the in-flight unit landed, persist it so the
            # next session resumes from this exact cursor
            self._autosave(config)
        return self

    @spanned("repro.finalize")
    def finalize(self) -> Clustering:
        """The clustering of everything ingested so far.  Does not consume
        the state — more ``partial_fit`` calls may follow.

        Backends with a ``finalize_fn`` (sweep, sharded) derive labels and
        the :class:`ClusterState` view from the current state; the others
        reuse the labels of the last ingested batch.
        """
        if self._backend.finalize_fn is not None:
            result = self._backend.finalize_fn(self._state, self.config)
        elif self._last_result is not None:
            result = self._last_result
        else:  # no batch ingested yet: every node is its own singleton
            result = self._backend.fn(_EMPTY_BATCH, self.config, self._state)
            self._state = result.state
        info = result.info
        if self._refine is not None and result.state is not None:
            # Multi-stage refinement (DESIGN.md §11): contract the streamed
            # communities through the accumulated sketch, refine the
            # supergraph, project back, optionally re-play the buffered
            # window.  Nothing is consumed — the sketch keeps accumulating
            # if more partial_fit calls follow.
            labels, state, info = self._refine.apply(
                np.asarray(result.labels), result.state, info, self.config
            )
            result = BackendResult(state=state, labels=labels, info=info)
        if self.stream_batches:  # surfaced like streamed cluster() calls
            info = dict(info)
            info["peak_buffer_bytes"] = self.peak_buffer_bytes
            info["stream_batches"] = self.stream_batches
            info["stream_dispatches"] = self.stream_dispatches
            if self.stream_megabatches:
                info["stream_megabatches"] = self.stream_megabatches
        if self.wavefront_megabatches:  # §12 counters (directly pushed
            info = dict(info)  # megabatches count too, so copy again here)
            if self._wavefront_stats is not None:
                live, fall = (int(x) for x in np.asarray(self._wavefront_stats))
            else:
                live = fall = 0
            info["wavefront_megabatches"] = self.wavefront_megabatches
            info["wavefront_waves"] = self.wavefront_waves
            info["wavefront_mean_wave_width"] = (
                self.wavefront_rows_in_waves / self.wavefront_waves
                if self.wavefront_waves
                else 0.0
            )
            info["wavefront_leftover_rows"] = self.wavefront_leftover_rows
            info["wavefront_dead_rows_skipped"] = (
                self.wavefront_dead_rows_skipped
            )
            info["wavefront_plan_seconds"] = self.wavefront_plan_seconds
            info["wavefront_live_waves"] = live
            info["wavefront_fallback_waves"] = fall
            info["wavefront_fallback_rate"] = fall / live if live else 0.0
            info["wavefront_widths"] = list(self.wavefront_widths)
        if (  # §15 resilience counters: surfaced whenever the machinery
            self.autosaves  # was active, even if every count is zero —
            or self.ingest_retries  # "nothing lost" is a reportable fact
            or self.ingest_stalls
            or self._quarantine_sources
        ):
            info = dict(info)
            info["autosaves"] = self.autosaves
            info["ingest_retries"] = self.ingest_retries
            info["ingest_stalls"] = self.ingest_stalls
            info["blocks_quarantined"] = sum(
                s.blocks_quarantined for s in self._quarantine_sources
            )
            info["edges_lost"] = sum(
                s.edges_lost for s in self._quarantine_sources
            )
        if self.device_decoded_megabatches:  # §14 counters
            info = dict(info)
            info["device_decoded_megabatches"] = self.device_decoded_megabatches
            info["device_fallback_rows"] = self.device_fallback_rows
            info["device_fallback_segments"] = self.device_fallback_segments
            info["device_total_segments"] = self.device_total_segments
            info["device_fallback_segment_rate"] = (
                self.device_fallback_segments / self.device_total_segments
                if self.device_total_segments
                else 0.0
            )
        # The device tiers *donate* their state buffers (chunked / pallas /
        # multiparam / sharded updates), so the live self._state — which
        # result.state/labels may alias via to_device() — is consumed by the
        # next partial_fit.  Snapshot the result to host so a finalized
        # Clustering outlives further ingestion, per this method's contract.
        with span("repro.finalize.fetch"):
            state = None if result.state is None else result.state.to_numpy()
            raw_labels = np.asarray(result.labels)
        return Clustering(
            state=state, config=self.config, raw_labels=raw_labels, info=info
        )

    # ------------------------------------------------------------------
    # Suspend / resume across sessions (repro.checkpoint.manager)
    # ------------------------------------------------------------------

    def save(self, directory: str) -> str:
        """Checkpoint state (step-atomic, step = edges seen) + config sidecar.

        The config is written first via atomic replace, so a preemption at
        any point leaves either a restorable checkpoint or a clean
        "no checkpoints" failure — never a state/config torn pair.  The
        stream cursor (row + opaque codec token, as a flat int64 leaf) is
        part of the checkpoint pytree itself, so state and stream position
        can never tear apart.  Wide states (sweep, sharded) are just wider
        pytrees — they ride the same manager, and so does the refinement
        runtime when ``config.refine`` is set: the supergraph sketch (and
        the replay window, for ``+replay``) becomes an extra leaf-set, so a
        resumed run's refinement is bit-identical to an uninterrupted one.
        """
        mgr = CheckpointManager(directory)  # creates the directory
        tmp = os.path.join(directory, _CONFIG_FILE + ".tmp")
        with open(tmp, "w") as f:
            f.write(self.config.to_json())
        os.replace(tmp, os.path.join(directory, _CONFIG_FILE))
        tree = {
            "cluster_state": self._state,
            "stream_cursor": self._cursor.to_array(),
        }
        if self._refine is not None:
            tree["refine"] = self._refine.to_leaves()
        return mgr.save(self.edges_seen, tree)

    @classmethod
    def restore(
        cls, directory: str, config: Optional[ClusterConfig] = None
    ) -> "StreamClusterer":
        """Resume from :meth:`save`; ``config`` overrides the saved one.

        An override may switch backends only within the same label space
        *and* state kind (dense → scan → pallas → chunked); an oracle state
        read as dense state — or a sweep pytree read as a 3n-int state —
        would silently mislabel, so both are rejected.
        """
        with open(os.path.join(directory, _CONFIG_FILE)) as f:
            saved = ClusterConfig.from_json(f.read())
        if config is None:
            config = saved
        else:
            saved_backend = get_backend(saved.backend)
            new_backend = get_backend(config.backend)
            if saved_backend.state_kind != new_backend.state_kind:
                raise ValueError(
                    f"cannot restore a {saved.backend!r} checkpoint "
                    f"({saved_backend.state_kind} state kind) with backend="
                    f"{config.backend!r} ({new_backend.state_kind} state "
                    "kind)"
                )
            if saved_backend.label_space != new_backend.label_space:
                raise ValueError(
                    f"cannot restore a {saved.backend!r} checkpoint "
                    f"({saved_backend.label_space} label space) with backend="
                    f"{config.backend!r} ({new_backend.label_space} label "
                    "space)"
                )
            if config.n_shards is None and saved.n_shards is not None:
                # shape fields the override leaves unset come from the saved
                # config, never from the restoring host's device count — the
                # checkpoint's shard axis is fixed on disk
                config = config.replace(n_shards=saved.n_shards)
        backend = get_backend(config.backend)
        config = _resolve_config(config, backend)
        mgr = CheckpointManager(directory)
        # Restore against a host-side, state-shape-aware template: the
        # backend's init_fn builds the right pytree kind (ClusterState /
        # SweepState / ShardedState) and numpy leaves come back with the
        # exact on-disk dtypes, so the int64 counters (edges_seen,
        # stream_offset) are not demoted to int32 the way device placement
        # would.  Device tiers re-place the state themselves (to_device).
        state_template = backend.init_fn(config).to_numpy()
        leaves = mgr.leaf_names()
        if "stream_cursor" in leaves:
            template = {
                "cluster_state": state_template,
                # variable-length leaf: the manager restores host leaves at
                # their on-disk shape, so any token width round-trips
                "stream_cursor": np.zeros(1, np.int64),
            }
            restored = mgr.restore(template)
            cursor = Cursor.from_array(restored["stream_cursor"])
        elif "stream_offset" in leaves:
            # pre-cursor checkpoint layout: a bare int64 raw-row offset —
            # restore it as a token-less cursor (always a valid position)
            template = {
                "cluster_state": state_template,
                "stream_offset": np.int64(0),
            }
            restored = mgr.restore(template)
            cursor = Cursor(int(restored["stream_offset"]))
        else:
            # pre-offset layout (state only): stream accounting from zero
            restored = mgr.restore({"cluster_state": state_template})
            cursor = Cursor(0)
        sc = cls(config, state=restored["cluster_state"])
        sc._cursor = cursor
        sc._last_autosave_row = cursor.row  # periodic saves resume from here
        if sc._refine is not None:
            # Refine leaves ride the same checkpoint (flattened as
            # refine_acc{i}_{kv,meta} / refine_replay_rows).  Restore them
            # only when the saved run recorded a matching set — an old or
            # refine-less checkpoint resumes with a fresh (empty) sketch,
            # which simply means the refinement only sees post-resume edges.
            n_accs = len(sc._refine.accumulators)
            acc_names = {
                f"refine_acc{i}_{part}"
                for i in range(n_accs)
                for part in ("kv", "meta")
            }
            if acc_names <= leaves:
                tmpl = {
                    f"acc{i}": {
                        "kv": np.zeros((0, 2), np.int64),
                        "meta": np.zeros(4, np.int64),
                    }
                    for i in range(n_accs)
                }
                if (
                    sc._refine.replay_buffer is not None
                    and "refine_replay_rows" in leaves
                ):
                    tmpl["replay"] = {"rows": np.zeros((0, 2), np.int32)}
                sc._refine.load_leaves(mgr.restore({"refine": tmpl})["refine"])
        return sc
